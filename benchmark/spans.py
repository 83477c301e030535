"""What one program span costs, by hand, on the chip:

    python3 -m benchmark.spans --cost

prints microseconds per ``with phase(...)`` with and without a profiler
session.  The spans themselves are the harness's: ``xtrace`` keeps and
reduces them, and ``run.py --trace 1`` prints their reconciliation with
the harness's own timing (``xtrace.reconcile``).  ``load`` is
``xtrace.load``, under the name the program's span tests import.
"""

from __future__ import annotations

import json
import sys
import time

from .xtrace import load  # noqa: F401  (the program's span tests read it)


def span_cost(n: int = 200_000) -> dict:
    """Microseconds per ``with phase(...)``: with no profiler session,
    with one, and a ``nullcontext`` for scale (host clock)."""
    import contextlib
    import tempfile

    import jax

    from ray_tpu.util.tracing import phase

    def per(make) -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            with make("delta.view"):
                pass
        return (time.perf_counter() - t0) / n * 1e6

    null = contextlib.nullcontext
    out = {"null_us": per(lambda _n: null()), "inactive_us": per(phase)}
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            out["active_us"] = per(phase)
        finally:
            jax.profiler.stop_trace()
    return out


def main(argv: list[str]) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--cost", action="store_true", required=True)
    ap.parse_args(argv)
    from . import harness
    harness.setup_env()
    print(json.dumps(span_cost()), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
