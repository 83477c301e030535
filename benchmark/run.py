"""One run of one cell:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's cluster and raylet from ``--seed`` and warms up
every shape the window uses; the window drives the raylet's placement
round back to back for ``--seconds``; then the sampled rounds are
compared with the reference.  The last line on standard output is one
JSON object with ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and with ``--trace 1`` ``breakdown``), and, last, ``checks``:
each number compared with its limit, also the last lines on standard
error.  Without the chips the cell asks for it exits non-zero and prints
no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

# the checkout's root, so that ``benchmark`` and ``ray_tpu`` import from it
sys.path[0] = str(Path(__file__).resolve().parent.parent)

from benchmark import check, harness  # noqa: E402


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    harness.setup_env()
    loaded = harness.load_cell(args.workload)
    record = harness.run_cell(loaded, args.seed, args.seconds,
                              bool(args.trace), platform="tpu",
                              t_start=T_START)
    print(json.dumps(result_line(record, loaded)), flush=True)
    return 0


def result_line(record: dict, loaded: dict) -> dict:
    checks = check.judge(record)
    device = dict(record["device"])
    out = {"correct": check.passed(checks, record),
           "attempted": record["offered"],
           "failed": record["offered"] - record["placed"]}
    if record["trace"]:
        summary = record.get("trace_summary") or {}
        out["metrics"] = harness.per_layer(record, loaded["per_layer"])
        device["busy_s"] = summary.get("busy_s", 0.0)
        device["window_s"] = summary.get("window_s", record["window_s"])
        out["breakdown"] = {"device_ops": summary.get("device_ops", []),
                            "idle_gaps": summary.get("idle_gaps", [])}
    else:
        e2e = harness.end_to_end(record)
        out["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
                          for m in loaded["end_to_end"]}
    out["device"] = device
    out["checks"] = checks
    print(f"rounds {len(record['round_s'])} window_s {record['window_s']} "
          f"compiles_in_window {record['compiles']['compiles']} "
          f"{record['compiles']['names']} setup_compiles "
          f"{record['setup_compiles']} "
          f"engine {record['engine']}",
          file=sys.stderr)
    if record["trace"]:
        from benchmark import xtrace
        print(f"reconcile {xtrace.reconcile(summary)}", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    return out


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
