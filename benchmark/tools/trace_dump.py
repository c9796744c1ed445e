"""Look at one trace by hand, and cut the recorded trace the test reads:

    python3 benchmark/tools/trace_dump.py --workload <cell> --seed <n>

Runs the cell once with the trace kept, prints its result line, then writes
to `chiprun_out/`: `trace_structure_<cell>.txt` (planes, lines, event counts,
the first events of each line with their stats) and
`trace_cut_<cell>.json` (the plain tuples `xtrace.load` makes, clipped to 80 ms
around the start of the first Pallas call, with the harness's marks): the latter is
what `tests/data/trace_cut.json` was copied from.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import run  # noqa: E402
import xtrace  # noqa: E402

CUT_SECONDS = 0.08


def structure(path: str, out) -> None:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        out.write(f"plane {plane.name!r}: {len(lines)} lines\n")
        for line in lines:
            events = list(line.events)
            out.write(f"  line {line.name!r}: {len(events)} events\n")
            for ev in events[:12]:
                try:
                    stats = {k: (v if not isinstance(v, (bytes, str))
                                 else str(v)[:160]) for k, v in ev.stats}
                except Exception as e:      # a stat jax cannot decode
                    stats = {"error": repr(e)}
                out.write(f"    {ev.name[:100]!r} start={ev.start_ns} "
                          f"dur={ev.duration_ns} stats={stats}\n")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()
    code, result, _ = run.run_cell(run.Manifest(), args.workload, args.seed,
                                   args.seconds, 1, keep_trace=True)
    if result is None:
        return code
    print(json.dumps(result), flush=True)
    out_dir = os.path.join(run.ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    trace_dir = os.path.join(run.ROOT, ".bench_trace", args.workload)
    path = xtrace.newest_xplane(trace_dir)
    print(f"xplane: {path} {os.path.getsize(path)} bytes", flush=True)
    with open(os.path.join(
            out_dir, f"trace_structure_{args.workload}.txt"), "w") as fh:
        structure(path, fh)
    tr = xtrace.load(trace_dir)
    mosaic = set(tr["mosaic"])
    first = min(e[1] for evs in tr["device"].values() for e in evs
                if e[0] in mosaic) - CUT_SECONDS / 4
    end = first + CUT_SECONDS
    marks = [e for e in tr["host"] if e[0].startswith("bench:")]
    def clip(e):
        start = max(e[1], first)
        return (e[0], start, min(e[1] + e[2], end) - start)

    cut = {"device": {p: [clip(e) for e in evs
                          if e[1] < end and e[1] + e[2] > first]
                      for p, evs in tr["device"].items()},
           "host": [e for e in tr["host"]
                    if e[1] <= end and e[1] + e[2] >= first][:2000] + marks,
           "mosaic": tr["mosaic"]}
    with open(os.path.join(out_dir, f"trace_cut_{args.workload}.json"),
              "w") as fh:
        json.dump(cut, fh)
    return code


if __name__ == "__main__":
    sys.exit(main())
