"""Fixtures of the benchmark's CPU tests (helpers in ``bench_smoke``)."""
import pytest

from bench_smoke import make_smoke_root


@pytest.fixture
def smoke_root(tmp_path):
    return make_smoke_root(tmp_path)


@pytest.fixture
def run_script(monkeypatch):
    """``bench/run.py`` as a module, with its process set-up (environment
    and JAX's persistent cache) left out: tests share their process."""
    from bench.harness import checkout
    from bench_smoke import load_run_module

    monkeypatch.setattr(checkout, "setup_process", lambda: None)
    monkeypatch.setattr(checkout, "enable_cache", lambda: None)
    return load_run_module()


@pytest.fixture
def cpu_peaks(monkeypatch):
    """Let the harness run on the CPU: the peaks table has no CPU entry,
    so the tests lend it the v5e's."""
    from bench.harness import peaks

    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))
