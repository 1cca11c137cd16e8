"""A whole run of the harness on the CPU: it refuses without a TPU, a sound
tiny run comes out correct, and a run whose timed path is broken
underneath comes out not correct."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from tiny_cell import ROOT, run_tiny
from bench.run import load_cell

CELL = "danube-s50w8.chat"


def _run_py(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed",
         str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.fixture(scope="module")
def sound():
    return run_tiny(CELL)


def test_sound_tiny_run_is_correct(sound):
    assert sound["correct"] is True
    assert sound["failed"] == 0 and sound["attempted"] > 0
    _, _, _, e2e, _ = load_cell(CELL)
    assert set(sound["metrics"]) == {m["name"] for m in e2e}
    assert list(sound)[-1] == "checks"
    check = sound["checks"]["mean_logit_gap"]
    assert check["value"] <= check["limit"]
    assert sound["notes"]["compiles_in_window"] == 0
    json.dumps(sound)


def test_traced_tiny_run_reads_host_metrics_and_no_device_ones(monkeypatch):
    from bench import cell
    # profile the last half of the window, as a full-length run does its
    # last 20 of 50 s: the host-clock readers read the requests due before
    monkeypatch.setattr(cell, "TRACE_S", 4.0)
    host = [{"name": n, "unit": "ms"}
            for n in ("gen_lag_p99_ms", "queue_wait_p90_ms")]
    result = run_tiny(CELL, seed=2**31 + 41, seconds=8.0, trace=True,
                      more_metrics=host)
    assert result["correct"] is True
    # host spans and stamps exist on the CPU; device metrics need a TPU
    # plane in the capture, and a reader that finds none reports nothing
    assert {"gen_lag_p99_ms", "queue_wait_p90_ms"} <= set(result["metrics"])
    assert not {"decode_step_ms", "gemm_roofline", "decode_mfu",
                "prefill_ms_per_ktok"} & set(result["metrics"])


def _altered_token(monkeypatch):
    from repro.serve.backend import ExecutionBackend
    inner = ExecutionBackend.decode_block

    def decode_block(self):
        block = inner(self).copy()
        block[-1] = (block[-1] + 1) % self.model.cfg.vocab
        return block
    monkeypatch.setattr(ExecutionBackend, "decode_block", decode_block)


def _state_unchanged(monkeypatch):
    from repro.serve.backend import ExecutionBackend
    inner = ExecutionBackend.decode_block

    def decode_block(self):
        import jax
        import jax.numpy as jnp
        saved = jax.tree_util.tree_map(jnp.copy, self.pool.caches)
        block = inner(self)
        self.pool.caches = saved       # the step's cache writes are lost
        return block
    monkeypatch.setattr(ExecutionBackend, "decode_block", decode_block)


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    result = run_tiny(CELL, seed=2**31 + 29)
    assert result["correct"] is False
    check = result["checks"]["mean_logit_gap"]
    assert check["value"] > check["limit"]
