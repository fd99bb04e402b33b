"""The peak table refuses unknown chips; the entry point refuses the CPU."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench.peaks import PEAKS, peak

ROOT = Path(__file__).resolve().parents[2]


def test_known_kind():
    assert peak("TPU v5 lite")["bf16_flops"] == 197e12
    assert PEAKS["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def test_unknown_kind_raises():
    with pytest.raises(KeyError, match="TPU v9"):
        peak("TPU v9")


def test_run_without_tpu_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen2-7b-d2.pack4k", "--seed", "2147483659",
                        "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
