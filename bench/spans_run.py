"""Run one cell once with ``--trace 1`` and read the program's spans and
model scopes from the trace (``bench/spans.py``).

    python bench/spans_run.py --workload <name> --seed <n> --seconds <s> \
        [--keep-trace <file>]

The run is ``run.py --trace 1``'s, through the same harness; only the trace
reduction differs: the trace keeps the ``repro.*`` host spans and the XLA
module intervals, the step's compiled HLO text maps each device op to its
model scope, and the result line adds the per-layer metrics of ``SPAN_METRICS``
and the ``breakdown`` keys ``idle_split_s``, ``idle_by_span``,
``idle_elsewhere`` and ``device_by_scope``, with each idle gap named by the
trainer thread's innermost span. ``--keep-trace`` writes the window's first
three steps of that trace (JSON). Without a TPU it exits with code 2.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]

SPAN_METRICS = [
    dict(name=name, unit="ms", better="lower", source="device_trace",
         layer=layer, moves="train_rows_per_s")
    for name, layer in (("idle_dispatch_ms", "train step"),
                        ("idle_readback_ms", "train step"),
                        ("idle_feed_ms", "feed client and prefetch"),
                        ("idle_unspanned_ms", "device"),
                        ("logits_device_ms", "train step"),
                        ("encoder_device_ms", "train step"))]


class Reduction:
    """Stands in for ``bench.trace`` in the harness: the trace with the
    program's spans, its op scopes from the step the run trained, and both
    summaries merged."""

    def __init__(self, harness):
        from bench import spans, trace

        self.spans, self.trace = spans, trace
        self.st = None
        self.summary = None
        first_steps = harness.first_steps

        def keep(*args, **kwargs):
            self.st = first_steps(*args, **kwargs)
            return self.st

        harness.first_steps = keep

    def from_xplane(self, path: str) -> dict:
        t = self.spans.from_xplane(path)
        batch = next(iter(self.st.tf.kept.values()))
        t["scopes"] = self.spans.op_scopes(
            self.st.trainer.step_hlo_text(batch))
        return t

    def crop(self, t: dict, steps: int) -> dict:
        return self.spans.crop(t, steps)

    def summarize(self, t: dict):
        base = self.trace.summarize(t)
        if base is not None:
            self.summary = {**base, **self.spans.summarize(t)}
        return self.summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep-trace", default=None)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["per_layer"] = bench["per_layer"] + SPAN_METRICS
    cell = harness.load_cell(args.workload, bench=bench)
    red = Reduction(harness)
    harness.trace_mod = red
    try:
        out = harness.run(cell, args.seed, args.seconds, True, T_START,
                          keep_trace=args.keep_trace,
                          say=lambda s: print(s, flush=True))
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if red.summary is not None:
        for key in ("idle_split_s", "idle_by_span", "idle_elsewhere",
                    "device_by_scope"):
            out["breakdown"][key] = red.summary[key]
    out["checks"] = out.pop("checks")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
