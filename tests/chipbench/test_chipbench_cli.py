"""``chipbench/run.py`` refuses a host without a TPU."""

import json
import os
import subprocess
import sys

from chipbench_tiny import ROOT


def test_exits_nonzero_without_a_tpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "qwen15-moe-a2.7b.chat", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout
    assert "TPU" in p.stderr
    for line in p.stdout.splitlines():
        assert "correct" not in json.loads(line) if line.startswith("{") \
            else True
