#!/usr/bin/env python3
"""The controls at a cell's own size, on the chip, several seeds in one
process (the benchmark's own runs never run this):

  python3 benchmarks/tests/control_on_chip.py --workload <cell> \\
      --fault flip|remove|wrong_read|none --seeds 1,2,3 --seconds 10

Each seed is one ``run.run_cell`` of the cell's own files with the fault
of ``faults.py`` planted underneath (``none``: the sound program).  One
line a seed: ``correct``, ``failed`` and the numbers compared that lie
outside their limits.  Exit code 0 when every run with a fault read not
``correct`` (with ``none``: every run ``correct``).
"""

import argparse
import asyncio
import contextlib
import json
import os
import shutil
import sys
import tempfile

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(os.path.abspath(__file__)), BENCH,
                os.path.dirname(BENCH)]

import faults  # noqa: E402
import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", choices=(*faults.KINDS, "none"), required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    spec = bench_run.load_cell(args.workload)
    device = bench_run.device_identity(spec["cell"]["chips"])
    if device is None:
        return 1
    from ceph_tpu.ops.compile_cache import ensure_persistent_cache
    from ceph_tpu.parallel import encode_service

    encode_service.shared()
    ensure_persistent_cache()
    as_wanted = True
    for seed in map(int, args.seeds.split(",")):
        data_dir = tempfile.mkdtemp(prefix="control-")
        try:
            with contextlib.nullcontext() if args.fault == "none" \
                    else faults.planted(args.fault):
                out = asyncio.run(bench_run.run_cell(
                    spec, seed=seed, seconds=args.seconds, trace=False,
                    data_dir=data_dir, device=device))
        finally:
            shutil.rmtree(data_dir, ignore_errors=True)
        outside = {k: x for k, x in out["compared"].items()
                   if not bench_run.verify.within({k: x})}
        print(json.dumps({
            "line": "control", "workload": args.workload,
            "fault": args.fault, "seed": seed, "correct": out["correct"],
            "attempted": out["attempted"], "failed": out["failed"],
            "outside_their_limits": outside}), flush=True)
        as_wanted &= out["correct"] == (args.fault == "none")
    return 0 if as_wanted else 1


if __name__ == "__main__":
    sys.exit(main())
