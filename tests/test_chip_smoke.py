"""chip_smoke.py off the chip: its refusal without a TPU, and its phases at
a tiny size on the CPU (kernels interpret there, so the check that the
compiled routes hold a Pallas kernel is replaced by one that they ran)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke as cs  # noqa: E402


def test_refuses_without_tpu():
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


@pytest.fixture
def cpu_smoke(monkeypatch, capsys):
    def ran(executor, route):
        kinds = {str(k[6]) for k, _, _ in executor.trace_log if k[0] == route}
        cs.check(bool(kinds), f"no {route} route ran")
        return dict.fromkeys(sorted(kinds), 1)
    monkeypatch.setattr(cs, "kernel_calls", ran)
    monkeypatch.setattr(cs, "mem", lambda dev, key="": 0)
    yield capsys


def _phases(out):
    return {p["phase"]: p for p in map(json.loads, out.splitlines())}


def test_one_chip_phases_on_cpu(cpu_smoke):
    from repro.configs.jag_billion import REDUCED
    cs.one_chip(jax.devices()[0], seed=0, log2n=12, cfg=REDUCED)
    ph = _phases(cpu_smoke.readouterr().out)
    assert set(ph["serve:range"]["routes"]) == {"prefilter", "graph",
                                                "postfilter"}
    assert ph["serve:range&subset"]["exact_equal"]["prefilter"] == cs.N_REF
    assert all(r.endswith("+delta") for r in ph["stream:range"]["routes"])
    assert ph["kernels"]["delta_rows"] == cs.DELTA_ROWS
    # the range batch and the compound range&subset batch each compiled
    # their own prefilter program
    assert len(ph["kernels"]["prefilter_tpu_custom_calls"]) == 2


def test_compare_flags_wrong_ids():
    import numpy as np
    ref = np.arange(cs.K)[None]
    routes = ("prefilter",)
    cs.compare("ok", ref, routes, ref, [0])
    with pytest.raises(cs.SmokeFailure, match="ids"):
        cs.compare("bad", ref[:, ::-1], routes, ref, [0])
    with pytest.raises(cs.SmokeFailure, match="recall"):
        cs.compare("low", ref + 100, ("graph",), ref, [0])
