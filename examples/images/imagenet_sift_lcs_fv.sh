#!/bin/bash
# Canonical ImageNetSiftLcsFV launch — the reference config shape
# (ImageNetSiftLcsFV.scala:146-167): descDim=64, vocabSize=16,
# lambda=6e-5, mixtureWeight=0.25, numPcaSamples=numGmmSamples=1e7, SIFT
# scaleStep 1, LCS stride 4 / border 16 / patch 6, 1000 classes at 256px.
# Tar-of-JPEG locations train on real data; absent, synthetic textures.
set -e
KEYSTONE_DIR="$( cd "$( dirname "${BASH_SOURCE[0]}" )" && pwd )"/../..
: ${EXAMPLE_DATA_DIR:=$KEYSTONE_DIR/example_data}

ARGS=(--descDim 64 --vocabSize 16 --lambda 6e-5 --mixtureWeight 0.25
      --numPcaSamples 10000000 --numGmmSamples 10000000 --numClasses 1000
      --siftScaleStep 1 --lcsStride 4 --lcsBorder 16 --lcsPatch 6
      --imageSize 256)
if [ -d "$EXAMPLE_DATA_DIR/imagenet-train" ]; then
  ARGS+=(--trainLocation "$EXAMPLE_DATA_DIR/imagenet-train"
         --testLocation "$EXAMPLE_DATA_DIR/imagenet-test"
         --labelsFile "$EXAMPLE_DATA_DIR/imagenet-labels")
fi
exec "$KEYSTONE_DIR/bin/run-pipeline.sh" ImageNetSiftLcsFV "${ARGS[@]}" "$@"
