#!/usr/bin/env python3
"""Find a cell's knee once, on the chip: load the configuration once, then
offer each rate for ``--seconds`` and report what share of the requests met
the cell's limits and whether the queue grew.

    python3 benchmark/sweep.py --cell m7b-chat=0.6,0.8,1.0,1.3 \
        --cell m7b-decode=8,16 --seconds 30 --seed 100 --out <file>

Cells given together share one configuration (one server, one load). A
closed-loop cell takes client counts where an open-loop one takes rates. The
knee is the highest rate at which at least 90% of the requests meet both
limits and the queue is no deeper at the window's end than a third into it;
the cell file then holds 0.8 x knee as a number. The same rate given several
times (each window takes the next seed) shows how far the cell's metrics
spread between seeds, without paying the load again. Not part of a benchmark
run: nothing here is read by run.py.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run as bench  # noqa: E402
from harness import metrics as mtr  # noqa: E402
from harness import spec  # noqa: E402
from harness.client import Client  # noqa: E402
from harness.server import HarnessFailure, Server  # noqa: E402


async def sweep(server: Server, cells: list, args, platform: str) -> list:
    bench.wait_ready(server, cells[0][0], platform)
    rows, seed = [], args.seed
    for cell, levels in cells:
        async with Client(server.base, server.name,
                          cell.traffic["sampling"], run_tag="warm") as client:
            t = time.monotonic()
            warm = await bench.warm_up(client, server, cell, args.seed)
            bench.say(f"{cell.name}: warm-up {time.monotonic() - t:.1f}s "
                      f"{warm}")
        key = "rate_rps" if cell.traffic["loop"] == "open" else "clients"
        for level in levels:
            seed += 1
            at = dataclasses.replace(cell, drive={
                **cell.drive, key: level if key == "rate_rps" else int(level)})
            traced: dict = {}
            async with Client(server.base, server.name,
                              cell.traffic["sampling"],
                              run_tag=f"s{seed}") as client:
                w = await bench.run_window(client, server, at, seed,
                                           args.seconds, False, traced)
                records = client.records
            loop = cell.traffic["loop"]
            attempted, failed = mtr.counts(records, w, loop)
            e2e = mtr.end_to_end(records, w, loop, 0.0)
            row = {
                "cell": cell.name, key: level, "seed": seed,
                "seconds": args.seconds, "attempted": attempted,
                "failed": failed,
                "attained": mtr.attained(records, w, loop,
                                         cell.drive["limits"]),
                "waiting_third": mtr.waiting_at(
                    records, w.t_open + w.seconds / 3),
                "waiting_end": mtr.waiting_at(records, w.t_close),
                "compiles_in_window": traced["compiles_close"]
                - traced["compiles_open"],
                # cumulative over the server's life: the speculation lane's
                # counters, for runs of a scratch configuration that leaves
                # ``engine.spec`` at the program's default
                "speculation": {n: v for n, _, v in server.metrics()
                                if n.startswith("localai_speculative")},
                **{k: v for k, v in e2e.items() if k != "setup_s"},
                # per request, so other limits can be tried on the same run
                "ttft_ms": [round(1e3 * r.ttft(), 1) for r in
                            mtr.scored(records, w, loop) if r.first],
                "tpot_ms": [round(1e3 * r.tpot(), 2) for r in
                            mtr.scored(records, w, loop) if r.tpot()],
            }
            rows.append(row)
            print("row " + json.dumps({k: v for k, v in row.items()
                                       if not isinstance(v, list) and v != {}}),
                  flush=True)
    return rows


def main(argv=None, *, platform: str = "tpu",
         root: Path = spec.ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", action="append", required=True,
                    metavar="NAME=LEVEL,LEVEL,...")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=100)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    cells = []
    for item in args.cell:
        name, _, levels = item.partition("=")
        cells.append((spec.load_cell(name, root),
                      [float(x) for x in levels.split(",")]))
    if len({c.config_name for c, _ in cells}) != 1:
        print("cells swept together must share a configuration",
              file=sys.stderr)
        return 2
    run_dir = (spec.bench_dir(root) / ".run"
               / f"sweep-{cells[0][0].config_name}")
    shutil.rmtree(run_dir, ignore_errors=True)
    server = Server(cells[0][0], root, run_dir, platform=platform)
    try:
        rows = asyncio.run(sweep(server, cells, args, platform))
    except BaseException as e:
        server.kill()
        if isinstance(e, (HarnessFailure, LookupError)):
            print(f"sweep failed: {e}", file=sys.stderr)
            return 1
        raise
    server.stop()
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(rows, indent=1) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
