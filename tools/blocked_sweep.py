"""On-chip block-size sweep for the BLOCKED flash attention path at
long sequence (the blocked online-softmax kernels have never been
in-model measured, and their 256/512 tiles were chosen at S=256
scale).

    python tools/blocked_sweep.py            # default tile grid
    python tools/blocked_sweep.py 256:512 128:512 256:1024

Each config re-execs the longseq bench in THIS process by setting
PALLAS_BLK_Q/K before (re)importing the kernels — the targets are
module-level constants, so each config runs in a fresh subprocess to
keep the measurement honest. One JSON line per config."""

import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = r"""
import json, sys
sys.path.insert(0, %r)
import bench
r = bench.bench_transformer_longseq()
r.pop("_mixes", None)
print("SWEEP_RESULT " + json.dumps(r), flush=True)
"""


def main():
    grids = sys.argv[1:] or ["256:512", "128:512", "256:1024",
                             "512:512", "128:1024"]
    for g in grids:
        bq, bk = g.split(":")
        env = dict(os.environ)
        env["PALLAS_BLK_Q"] = bq
        env["PALLAS_BLK_K"] = bk
        p = subprocess.run([sys.executable, "-c", _CHILD % _REPO],
                           env=env, capture_output=True, text=True,
                           timeout=2400)
        row = {"blk_q": int(bq), "blk_k": int(bk)}
        for line in p.stdout.splitlines():
            if line.startswith("SWEEP_RESULT "):
                row.update(json.loads(line[len("SWEEP_RESULT "):]))
                break
        else:
            row["error"] = (p.stderr or p.stdout)[-500:]
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
