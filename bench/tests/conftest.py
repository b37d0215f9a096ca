"""Fixtures of the benchmark's CPU tests: a test-only data root and a
clean tuning database around every run of the serving runner."""
import pathlib

import pytest

DATA_ROOT = pathlib.Path(__file__).resolve().parent / "data" / "root"


@pytest.fixture
def data_root():
    return DATA_ROOT


@pytest.fixture
def clean_tuning():
    """A fresh default tuning database, thawed and restored afterwards."""
    from repro import tuning_cache
    from repro.tuning_cache import TuningDatabase
    tuning_cache.thaw()
    tuning_cache.set_default_db(TuningDatabase())
    try:
        yield
    finally:
        tuning_cache.thaw()
        tuning_cache.reset_default_db()


CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
