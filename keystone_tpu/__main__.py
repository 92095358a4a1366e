"""The CLI front door: ``python -m keystone_tpu <PipelineName> [args...]``.

Parity: ``bin/run-pipeline.sh:34-56`` + ``run-main.sh`` in the reference —
one entry point that dispatches a pipeline class name to its ``main``. The
reference's ``--master``/SPARK_HOME switch becomes ``--backend tpu|cpu``:
the jax platform is selected before any device is initialized, with the
CPU backend optionally widened to a virtual N-device mesh (the local-mode
stand-in for a slice, like ``local[n]``).

Pipeline names match the reference application objects, e.g.::

    python -m keystone_tpu MnistRandomFFT --numFFTs 4 --blockSize 2048
    python -m keystone_tpu RandomPatchCifar --numFilters 100
    python -m keystone_tpu LinearPixels          # cifar-extras family
    python -m keystone_tpu VOCSIFTFisher --trainLocation voc.tar ...
"""

from __future__ import annotations

import argparse
import sys
from typing import Callable, List, Optional


def _mnist(argv):
    from .pipelines.mnist_random_fft import main

    return main(argv)


def _random_patch_cifar(argv):
    from .pipelines.random_patch_cifar import main

    return main(argv)


def _cifar_extra(app: str) -> Callable:
    def run(argv):
        from .pipelines.cifar_extras import main

        return main([app, *argv])

    return run


def _voc(argv):
    from .pipelines.voc_sift_fisher import main

    return main(argv)


def _imagenet(argv):
    from .pipelines.imagenet_sift_lcs_fv import main

    return main(argv)


def _timit(argv):
    from .pipelines.timit import main

    return main(argv)


def _newsgroups(argv):
    from .pipelines.newsgroups import main

    return main(argv)


def _amazon(argv):
    from .pipelines.amazon_reviews import main

    return main(argv)


def _stupid_backoff(argv):
    from .pipelines.stupid_backoff_pipeline import main

    return main(argv)


#: shorthand → reference application object name (the full names stay the
#: canonical registry keys; these are CLI conveniences only)
ALIASES = {
    "mnist": "MnistRandomFFT",
    "cifar": "RandomPatchCifar",
    "voc": "VOCSIFTFisher",
    "imagenet": "ImageNetSiftLcsFV",
    "timit": "TimitPipeline",
    "newsgroups": "NewsgroupsPipeline",
    "amazon": "AmazonReviewsPipeline",
}

#: reference application object name → runner
PIPELINES = {
    "MnistRandomFFT": _mnist,
    "LinearPixels": _cifar_extra("LinearPixels"),
    "RandomCifar": _cifar_extra("RandomCifar"),
    "RandomPatchCifar": _random_patch_cifar,
    "RandomPatchCifarAugmented": _cifar_extra("RandomPatchCifarAugmented"),
    "RandomPatchCifarKernel": _cifar_extra("RandomPatchCifarKernel"),
    "VOCSIFTFisher": _voc,
    "ImageNetSiftLcsFV": _imagenet,
    "TimitPipeline": _timit,
    "NewsgroupsPipeline": _newsgroups,
    "AmazonReviewsPipeline": _amazon,
    "StupidBackoffPipeline": _stupid_backoff,
}


def _select_backend(backend: Optional[str], cpu_devices: int) -> None:
    """Pick the jax platform BEFORE any device is touched. A platform
    asked for by name is never quietly swapped for another: jax raises
    at backend start-up when it cannot be had, in this process and — the
    ``ClusterRouter`` boot spec carries ``jax.config.jax_platforms`` — in
    every worker it starts. Without ``--backend`` jax keeps its own
    choice (or ``JAX_PLATFORMS``);
    :func:`~keystone_tpu.parallel.mesh.report_platform` says which
    platform that was."""
    if cpu_devices > 1 and backend != "cpu":
        import logging

        logging.getLogger(__name__).warning(
            "--cpuDevices %d has no effect without --backend cpu "
            "(virtual devices exist only on the cpu backend)", cpu_devices,
        )
    if backend is None:
        return
    if backend == "cpu" and cpu_devices > 1:
        from .parallel.virtual import provision_virtual_devices

        provision_virtual_devices(cpu_devices)
        return
    import jax

    jax.config.update("jax_platforms", backend)


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    p = argparse.ArgumentParser(
        prog="python -m keystone_tpu",
        description="Run a pipeline (parity: bin/run-pipeline.sh).",
    )
    # Pre-scan for the demo modes: in demo mode there is no pipeline
    # positional, and the demo's own flags (--requests 64, ...) must pass
    # through parse_known_args without a positional slot swallowing their
    # values. Accept the same unambiguous prefix abbreviations argparse
    # would (--serve, --train, --sweep-d, ...); a prefix shared with ANY
    # other registered flag (--s, --tra vs --trace) matches no demo flag
    # and falls through to argparse's ambiguity error.
    _DEMO_FLAGS = ("--serve-demo", "--sweep-demo", "--trainer-demo")
    #: every other long option registered below — a demo abbreviation
    #: must be unambiguous against these too, exactly as argparse would
    #: treat it (--tra must stay an error between --trace/--trainer-demo)
    _OTHER_FLAGS = (
        "--backend", "--cpuDevices", "--log", "--logLevel",
        "--check", "--trace", "--aot-cache", "--profiles",
    )

    def _is_demo_flag(a: str, flag: str) -> bool:
        return (
            len(a) > 2
            and flag.startswith(a)
            and sum(f.startswith(a) for f in _DEMO_FLAGS) == 1
            and not any(f.startswith(a) for f in _OTHER_FLAGS)
        )

    def _is_serve_demo_flag(a: str) -> bool:
        return _is_demo_flag(a, "--serve-demo")

    def _is_sweep_demo_flag(a: str) -> bool:
        return _is_demo_flag(a, "--sweep-demo")

    def _is_trainer_demo_flag(a: str) -> bool:
        return _is_demo_flag(a, "--trainer-demo")

    serve_demo = any(_is_serve_demo_flag(a) for a in argv)
    sweep_demo = any(_is_sweep_demo_flag(a) for a in argv)
    trainer_demo = any(_is_trainer_demo_flag(a) for a in argv)
    argv = [
        a for a in argv
        if not any(_is_demo_flag(a, f) for f in _DEMO_FLAGS)
    ]
    # registered for -h only; the flags themselves are consumed above
    p.add_argument(
        "--serve-demo", action="store_true", dest="serve_demo",
        help="smoke mode: fit a small pipeline and push synthetic traffic "
             "through the serving engine (see keystone_tpu/serving/); "
             "replaces the pipeline name. --replicas N serves from a "
             "continuous-batching ServingFleet of N workers instead of "
             "the single-worker engine; --workers N (or KEYSTONE_WORKERS) "
             "serves from a multi-process ClusterRouter of N worker "
             "processes sharing the AOT cache for warm boots "
             "(keystone_tpu/cluster/)",
    )
    p.add_argument(
        "--sweep-demo", action="store_true", dest="sweep_demo",
        help="smoke mode: fit a λ grid as ONE merged DAG "
             "(keystone_tpu/sweep/), absorb appended chunks into the best "
             "member, and hot-swap it into a live serving engine; "
             "replaces the pipeline name",
    )
    p.add_argument(
        "--trainer-demo", action="store_true", dest="trainer_demo",
        help="smoke mode: the closed continual-learning loop "
             "(keystone_tpu/trainer/) — boot a replica fleet + trainer "
             "daemon, append chunk batches under live traffic, and "
             "assert promoted refreshes, a clean canary rollback of a "
             "poisoned batch, and zero request failures; replaces the "
             "pipeline name",
    )
    if not (serve_demo or sweep_demo or trainer_demo):
        # validated by _resolve_pipeline, not choices=, so shorthand
        # aliases (mnist, cifar, ...) and any-case names resolve
        p.add_argument(
            "pipeline", metavar="pipeline",
            help="one of: " + ", ".join(sorted(PIPELINES))
                 + " (case-insensitive; shorthands: "
                 + ", ".join(sorted(ALIASES)) + ")",
        )
    p.add_argument(
        "--backend", choices=["tpu", "cpu"], default=None,
        help="jax platform; default = whatever jax picks",
    )
    p.add_argument(
        "--cpuDevices", type=int, default=1,
        help="with --backend cpu: virtual device count for a local mesh",
    )
    p.add_argument(
        "--log", "--logLevel", dest="log_level", default=None,
        choices=["debug", "info", "warning", "error"],
        help="log verbosity (default: $KEYSTONE_LOG or warning)",
    )
    p.add_argument(
        "--check", action="store_true", dest="check_only",
        help="static-check mode: build the pipeline, run the whole-DAG "
             "shape/dtype/traceability checker and segment planner "
             "(keystone_tpu/check/) at fit entry, print the report, and "
             "exit WITHOUT executing a single chunk or sample; non-zero "
             "exit on a statically-proven defect",
    )
    p.add_argument(
        "--trace", default=None, metavar="PATH",
        help="record a per-node execution trace and write Chrome-trace "
             "JSON to PATH — open in chrome://tracing or "
             "https://ui.perfetto.dev (also: KEYSTONE_TRACE=PATH)",
    )
    p.add_argument(
        "--aot-cache", default=None, metavar="DIR", dest="aot_cache",
        help="persistent AOT executable cache directory: fitted-pipeline "
             "compiles load previously exported executables instead of "
             "re-tracing, so warm boots skip every compile "
             "(also: KEYSTONE_AOT_CACHE=DIR)",
    )
    p.add_argument(
        "--profiles", default=None, metavar="DIR", dest="profiles",
        help="persistent operator-profile store directory: fits learn "
             "per-operator throughput from traced runs, and the second "
             "fit of a pipeline plans solver choice + caching from the "
             "stored evidence with zero sampling executions "
             "(also: KEYSTONE_PROFILE_DIR=DIR)",
    )
    args, rest = p.parse_known_args(argv)
    if not (serve_demo or sweep_demo or trainer_demo):
        name = _resolve_pipeline(p, args.pipeline)
    from .utils.obs import configure, export_trace

    configure(
        args.log_level, trace=args.trace,
        aot_cache=args.aot_cache, profiles=args.profiles,
    )
    _select_backend(args.backend, args.cpuDevices)
    if not serve_demo:
        # the serve demo reports for itself: with --workers the parent
        # must stay off jax, and only its own parser knows
        from .parallel.mesh import report_platform

        report_platform()
    if args.check_only:
        from . import check as check_mod

        check_mod.set_check_only(True)
    try:
        try:
            if serve_demo:
                from .serving.demo import main as serve_demo_main

                return serve_demo_main(rest)
            if sweep_demo:
                from .sweep.demo import main as sweep_demo_main

                return sweep_demo_main(rest)
            if trainer_demo:
                from .trainer.demo import main as trainer_demo_main

                return trainer_demo_main(rest)
            return PIPELINES[name](rest)
        except Exception as e:
            from . import check as check_mod

            if args.check_only and isinstance(e, check_mod.CheckOnlyExit):
                s = e.report.summary()
                print(
                    f"CHECK OK: {s['nodes']} nodes, {s['segments']} "
                    f"segment(s), {s['barriers']} barrier(s), "
                    f"0 executions"
                )
                return 0
            raise
    finally:
        if args.check_only:
            from . import check as check_mod

            # in-process callers (tests) must not leak check-only mode
            check_mod.set_check_only(False)
        # no-op unless --trace/KEYSTONE_TRACE configured tracing; writing
        # here (not only atexit) means in-process callers get the file too
        export_trace()


def _resolve_pipeline(parser: argparse.ArgumentParser, name: str) -> str:
    if name in PIPELINES:
        return name
    lowered = {k.lower(): k for k in PIPELINES}
    full = ALIASES.get(name.lower()) or lowered.get(name.lower())
    if full is None:
        parser.error(
            f"argument pipeline: invalid choice: {name!r} "
            f"(choose from {', '.join(sorted(PIPELINES))})"
        )
    return full


if __name__ == "__main__":
    raise SystemExit(main())
