"""The runner refuses a machine without the chip: non-zero exit, no result."""
import os
import subprocess
import sys

import bench


def test_cpu_only_machine_exits_nonzero_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(bench.HERE / "run.py"), "--workload", "ccat_train",
                          "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
                         cwd=bench.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_percentile_is_nearest_rank():
    import run

    assert run.percentile(list(range(1, 101)), 99) == 99
    assert run.percentile(list(range(1, 201)), 99) == 198
    assert run.percentile([5.0], 99) == 5.0
