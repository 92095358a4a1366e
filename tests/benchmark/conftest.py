import pytest


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A tiny benchmark of its own, written as files (see ``tiny.py``)."""
    from tests.benchmark import tiny

    return tiny.build(str(tmp_path_factory.mktemp("bench")))
