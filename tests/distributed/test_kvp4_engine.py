"""KVP=4 serving through DecodeEngine on four host devices, in a
subprocess so the fake-device XLA flag never reaches this session (as in
test_multidev.py)."""
import os
import subprocess
import sys
from pathlib import Path

SCRIPT = Path(__file__).parent / "scripts" / "kvp4_engine.py"
SRC = str(Path(__file__).resolve().parents[2] / "src")


def test_kvp4_engine_split_carry_matches_one_device():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, str(SCRIPT)], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0 and "ALL OK" in p.stdout, (
        f"stdout:\n{p.stdout}\nstderr:\n{p.stderr}")
