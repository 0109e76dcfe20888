"""A run leaves no process behind: whatever the runtime's workers fork
into sessions of their own falls to the benchmark's process, which kills
and reaps it before it exits."""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

# a parent that exits at once and leaves two orphans in sessions of their
# own: one that runs on, as a worker that is slow to stop does, and one
# that has ended and only wants reaping
SCRIPT = '''
import json, subprocess, sys, time
sys.path.insert(0, sys.argv[1])
from benchmarks.harness import runtime

runtime.adopt_descendants()
subprocess.run(["sh", "-c", "(setsid sleep 300 &); (setsid true &); exit 0"],
               check=True)
time.sleep(0.3)
adopted = sorted(c for _, _, c in runtime._descendants().values())
killed = runtime.end_descendants()
print(json.dumps({"adopted": adopted, "killed": killed,
                  "left": len(runtime._descendants())}))
'''


def test_orphans_are_adopted_killed_and_reaped():
    out = subprocess.run([sys.executable, "-c", SCRIPT, ROOT],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr[-2000:]
    seen = json.loads(out.stdout.strip().splitlines()[-1])
    assert "sleep 300" in seen["adopted"]       # not init's: ours
    assert seen["killed"] == ["sleep 300"]      # the zombie is not named
    assert seen["left"] == 0
    alive = subprocess.run(["pgrep", "-f", "^sleep 300$"],
                           capture_output=True, text=True)
    assert not alive.stdout.strip()
