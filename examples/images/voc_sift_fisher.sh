#!/bin/bash
# Canonical VOCSIFTFisher launch (parity: examples/images/voc_sift_fisher.sh).
# Points at the VOC trainval/test tars + label CSV when present, and passes
# the published widths (the reference's script: descDim=80, vocabSize=256,
# lambda=0.5); the pipeline's own defaults are a toy's.
set -e
KEYSTONE_DIR="$( cd "$( dirname "${BASH_SOURCE[0]}" )" && pwd )"/../..
: ${EXAMPLE_DATA_DIR:=$KEYSTONE_DIR/example_data}

ARGS=()
if [ -f "$EXAMPLE_DATA_DIR/VOCtrainval_06-Nov-2007.tar" ]; then
  ARGS+=(--trainLocation "$EXAMPLE_DATA_DIR/VOCtrainval_06-Nov-2007.tar"
         --testLocation "$EXAMPLE_DATA_DIR/VOCtest_06-Nov-2007.tar"
         --labelPath "$EXAMPLE_DATA_DIR/voclabels.csv")
fi
exec "$KEYSTONE_DIR/bin/run-pipeline.sh" VOCSIFTFisher \
  --descDim 80 --vocabSize 256 --lambda 0.5 "${ARGS[@]}" "$@"
