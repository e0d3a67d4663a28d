"""Launch `repro.distributed.verify_sharded` in its own process.

The verifier must own its process because the forced device count is
fixed at jax init (and importing the module sets XLA_FLAGS).  The test
suite, the Table-3 benchmark, and the CI sharding job all go through
this one helper so the invocation recipe cannot drift.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))


def run_verifier(timeout: int = 540) -> list[dict]:
    """Run the 8-device sharded-forward sweep; return its result cells.

    Raises RuntimeError (with the subprocess stderr tail) on a non-zero
    exit — callers decide whether that is fatal.
    """
    root = repo_root()
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(root, "src") + os.pathsep +
                         env.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)      # the verifier sets its own
    env["JAX_PLATFORMS"] = "cpu"    # forced host devices; never the chip
    proc = subprocess.run(
        [sys.executable, "-m", "repro.distributed.verify_sharded",
         "--json"],
        capture_output=True, text=True, env=env, cwd=root, timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-4000:])
    return json.loads(proc.stdout)
