"""Run ``drive.py`` in a child process (its own JAX, four CPU devices)."""
import json
import subprocess
import sys
from pathlib import Path

DRIVE = Path(__file__).resolve().parent / "drive.py"


def drive_logged(*args, timeout=900):
    """The result lines of a ``drive.py`` run, and its standard error."""
    out = subprocess.run([sys.executable, str(DRIVE), *map(str, args)],
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return ([json.loads(line) for line in out.stdout.splitlines()
             if line.startswith("{")], out.stderr)


def drive(*args, timeout=900):
    return drive_logged(*args, timeout=timeout)[0]
