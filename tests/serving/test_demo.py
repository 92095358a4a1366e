"""The --serve-demo CLI path (what bin/serve-smoke.sh runs) and the
--log observability flag."""

import logging

from keystone_tpu.__main__ import main


def test_serve_demo_smoke(capsys):
    rc = main([
        "--serve-demo", "--backend", "cpu",
        "--requests", "16", "--nTrain", "512",
        "--numFFTs", "2", "--blockSize", "256", "--buckets", "8",
    ])
    out = capsys.readouterr().out
    assert rc == 0
    assert "SERVE PASS" in out
    assert "compiles=1" in out  # one bucket, one compile


def test_log_flag_levels_root_logger(capsys):
    prior = logging.getLogger().level
    try:
        rc = main([
            "--serve-demo", "--backend", "cpu", "--log", "error",
            "--requests", "8", "--nTrain", "256",
            "--numFFTs", "2", "--blockSize", "256", "--buckets", "8",
        ])
        assert rc == 0
        assert logging.getLogger().level == logging.ERROR
        # --logLevel stays as a back-compat alias of --log
        rc = main([
            "--serve-demo", "--backend", "cpu", "--logLevel", "warning",
            "--requests", "8", "--nTrain", "256",
            "--numFFTs", "2", "--blockSize", "256", "--buckets", "8",
        ])
        assert rc == 0
        assert logging.getLogger().level == logging.WARNING
    finally:
        logging.getLogger().setLevel(prior)
