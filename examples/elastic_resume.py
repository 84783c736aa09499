"""Elastic restore: train on an 8-device mesh, crash, resume on 4 devices.

Block-based checkpoints make recovery onto a DIFFERENT device count a
metadata remap (DESIGN.md §3): this script spawns the two phases as
subprocesses with different forced host-device counts.  The children
run on the CPU backend: the forced device count is a CPU-only flag, and
on a machine with a TPU two processes cannot share the chip.

    PYTHONPATH=src python examples/elastic_resume.py
"""

import os
import subprocess
import sys
import tempfile
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PHASE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ndev}"
import sys
sys.path.insert(0, {src!r})
from repro.launch.train import main
out = main(["--arch", "gemma_2b", "--reduced", "--steps", "{steps}",
            "--batch", "4", "--seq", "32", "--lr", "1e-3",
            "--ckpt-dir", {ckpt!r}, "--ckpt-every", "10",
            "--model-parallel", "{mp}"])
print("PHASE_DONE", out["losses"][-1])
"""


def run_phase(ndev, mp, steps, ckpt):
    prog = PHASE.format(ndev=ndev, mp=mp, steps=steps, ckpt=ckpt,
                        src=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, cwd=ROOT,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    if r.returncode != 0:
        print(r.stderr[-2000:])
        raise SystemExit(1)
    for line in r.stdout.splitlines():
        if line.startswith(("[", "PHASE_DONE", "final")):
            print("   ", line)


def main():
    ckpt = tempfile.mkdtemp(prefix="elastic_")
    print("phase 1: 8 devices (data=4, model=2), 20 steps")
    run_phase(8, 2, 20, ckpt)
    print("phase 2: 'cluster shrank' -> 4 devices (data=2, model=2), "
          "resume to 40")
    run_phase(4, 2, 40, ckpt)
    print("elastic resume complete: same checkpoints, different mesh.")


if __name__ == "__main__":
    main()
