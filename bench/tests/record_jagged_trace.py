"""Record the fixtures ``jagged_trace.json.gz`` and ``jagged_hlo.txt.gz``: the
program's jagged->padded Pallas kernel run under ``jax.named_scope(SCOPE)``
in a small jitted function, traced on a TPU and reduced as a traced run of
the benchmark reduces its step (``bench.trace.from_xplane``, the compiled
HLO text through ``bench.spans.op_scopes``, ``bench.trace.crop``). Each
traced step sleeps 5 ms before and after its call, so that each call's
device time falls inside its own step although the trace's device clock and
host clock disagree by up to a few milliseconds; the record prints by how
much (``clock``).

    python bench/tests/record_jagged_trace.py --out <dir>

Without a TPU it exits with code 2. ``test_kernel_trace.py`` reads the
fixtures on the CPU.
"""
from __future__ import annotations

import argparse
import glob
import gzip
import json
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
SCOPE = "densify"
BATCH, MAX_LEN, DIM = 256, 200, 64          # BERT4Rec's batch and window
STEPS = 3


def lengths() -> np.ndarray:
    """Each row's valid length, about 90-180 as in ``bert4rec.short_seq``."""
    return np.random.default_rng(7).integers(90, 181, BATCH)


def cost(itemsize: int = 4):
    """``(flops, bytes)`` of one densify: every byte of its operands (the
    values and the offsets) and of its result (the padded block); no
    arithmetic."""
    values = int(lengths().sum()) * DIM * itemsize
    offsets = (BATCH + 1) * 4
    return 0.0, float(values + offsets + BATCH * MAX_LEN * DIM * itemsize)


def step(values, offsets):
    import jax

    from repro.kernels.jagged.ops import jagged_to_padded

    with jax.named_scope(SCOPE):
        return jagged_to_padded(values, offsets, MAX_LEN)


def record(out: Path) -> dict:
    """Trace ``STEPS + 1`` calls after a warm-up; write the fixtures (the
    trace cropped to ``STEPS`` steps) and return the reduction's readings."""
    import jax
    import jax.numpy as jnp

    from bench import spans, trace

    n = lengths()
    offsets = jnp.asarray(np.concatenate([[0], np.cumsum(n)]), jnp.int32)
    values = jax.random.normal(jax.random.PRNGKey(0), (int(n.sum()), DIM))
    f = jax.jit(step)
    hlo = f.lower(values, offsets).compile().as_text()
    f(values, offsets).block_until_ready()
    tdir = tempfile.mkdtemp(prefix="jagged_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(tdir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(STEPS + 1):
            with jax.profiler.TraceAnnotation("bench.train_step"):
                time.sleep(0.005)
                with jax.profiler.TraceAnnotation("bench.call"):
                    f(values, offsets).block_until_ready()
                time.sleep(0.005)
    jax.profiler.stop_trace()
    path, = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)
    t = trace.from_xplane(path)
    clock = _clock(t)
    t["scopes"] = spans.op_scopes(hlo, spans.DEFAULT_SCOPES + (SCOPE,))
    t = trace.crop(t, STEPS)
    out.mkdir(parents=True, exist_ok=True)
    (out / "jagged_trace.json.gz").write_bytes(
        gzip.compress(json.dumps(t).encode(), mtime=0))
    (out / "jagged_hlo.txt.gz").write_bytes(
        gzip.compress(hlo.encode(), mtime=0))
    s = spans.summarize(t)
    return {
        "platform": jax.devices()[0].platform,
        "clock": clock,
        "custom_calls": [ln.strip()[:400] for ln in hlo.splitlines()
                         if "custom-call(" in ln],
        "scopes": t["scopes"],
        "device_by_scope": s and s["device_by_scope"],
        "steps": s and s["steps"],
        "busy_s": s and s["busy_s"],
    }


def _clock(t: dict) -> list:
    """For each traced call, ``[early, late]`` in microseconds: by how much
    its program's device interval starts before the host made the call and
    ends after the call returned (both negative on clocks that agree)."""
    calls = sorted(ev[1:] for p in t["planes"] for ln in p["lines"]
                   for ev in ln["events"] if ev[0] == "bench.call")
    runs = sorted(ev[1:] for evs in t["modules"].values() for ev in evs)
    out = []
    for s, d in runs:
        c = min(calls, key=lambda c: abs(c[0] - s))
        out.append([(c[0] - s) / 1e3, (s + d - c[0] - c[1]) / 1e3])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    if jax.devices()[0].platform != "tpu":
        print("record_jagged_trace: no TPU", file=sys.stderr)
        return 2
    print(json.dumps(record(Path(args.out)), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
