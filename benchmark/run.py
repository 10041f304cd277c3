#!/usr/bin/env python3
"""The benchmark's one entry:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

runs one cell of ``BENCHMARK.json`` on the chip this process finds and
prints, as the last line of stdout, one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (and ``breakdown``
with ``--trace 1``), then ``checks``: each number compared with the plain
reference beside its limit (also the last lines of stderr).

The cell's configuration names its harness (``benchmark/harness/<name>.py``)
and its traffic kind; the traffic file holds the mix's parameters. Without
a TPU, with fewer chips than the cell asks for, or without the program in
the checkout, it prints no result and exits 3.

``--rehearse`` runs the cell on the CPU at the traffic file's rehearsal
sizes; its line is prefixed so that it can never pass for a chip result.
``--seeds a,b,...`` runs several seeds in one process, for the dozen-seed
correctness sweep (set-up is then paid once). ``--control`` puts the
control in the program's place (see PERF.md): its numbers must fail.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import common  # noqa: E402


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seeds", default=None, help="comma-separated seeds, one process")
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    try:
        bench, cell, config, traffic = common.load_cell(args.workload)
        traffic = dict(traffic, name=cell["traffic"])
        if args.rehearse:
            traffic.update(traffic.get("rehearsal", {}))
        seconds = args.seconds if args.seconds is not None else bench["run_seconds"]
        device = common.open_platform(int(cell["chips"]), args.rehearse)
    except (common.Refused, OSError, KeyError, ValueError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    common.say("device", device)
    harness = importlib.import_module(f"benchmark.harness.{config['harness']}")
    seeds = [int(s) for s in args.seeds.split(",")] if args.seeds else [args.seed]
    if args.control:
        for seed in seeds:
            body, checks = harness.control(bench, cell, config, traffic, seed, device)
            common.emit(dict(body, seed=seed), checks, "control: ")
        return 0
    for k, seed in enumerate(seeds):
        try:
            body, checks = harness.run(bench, cell, config, traffic, seed, seconds,
                                       bool(args.trace), args.rehearse,
                                       T_START if k == 0 else common.wall(), device)
        except common.Refused as e:
            print(f"benchmark: {e}", file=sys.stderr)
            return 3
        except Exception:
            traceback.print_exc()
            return 1
        if args.seeds:
            body["seed"] = seed
        prefix = "rehearsal (CPU, not a chip result): " if args.rehearse else ""
        common.emit(body, checks, prefix or ("sweep: " if args.seeds else ""))
    return 0


if __name__ == "__main__":
    sys.exit(main())
