"""The readings the check's limits are set from; no run of the benchmark
calls this.

    python bench/calibrate.py --workload <name> --seeds 11,12,13

For each seed, one process does the run's own set-up through the first
three steps (the program's readings), then rebuilds those batches from the
source of truth and runs the float32 reference, and a planted fault in the
program's place: the reference over the first half of each batch only, the
mean taken over that half. ``--set compute_dtype='"bfloat16"'`` reads the
program on its bfloat16 path, the control of a float32 configuration. One
JSON line per seed, then a summary: the largest and smallest reading of the program
and the smallest of the fault, for each number compared.
"""
import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--set", action="append", default=[],
                    help="KEY=JSON: a configuration key to change, to read "
                         "the program on another of its paths")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from bench import harness, reference

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    sets = dict(kv.split("=", 1) for kv in args.set)
    cell = harness.load_cell(args.workload, overrides={
        "config": {k: json.loads(v) for k, v in sets.items()}})
    c, model = cell.config, cell.model
    say = lambda s: print(s, file=sys.stderr, flush=True)
    lines = []
    say("leaves: " + ", ".join(
        jax.tree_util.keystr(p) for p, _ in
        jax.tree_util.tree_flatten_with_path(model.param_shapes(c),
                                             is_leaf=lambda x: isinstance(
                                                 x, tuple) and len(x) == 2
                                             and isinstance(x[1], str))[0]))
    for seed in [int(s) for s in args.seeds.split(",")]:
        st = harness.first_steps(cell, seed, 0.0, say)
        st.feed.close(timeout=0.5)
        st.trainer = None
        gc.collect()
        line = {"seed": seed, **harness.rows_and_epochs(cell, st, seed)}
        batches = harness.reference_batches(cell, st, seed)
        run = lambda **kw: reference.reference_steps(
            model, c, lambda: st.init(st.key), batches, c["optimizer"],
            int(c["reference_block_rows"]), **kw)
        ref = run()
        line["program"] = reference.compare_training(st.prog, ref)
        line["half_batch"] = reference.compare_training(
            run(rows=st.batch // 2), ref)
        line["losses"] = {"program": st.prog["losses"],
                          "reference": ref["losses"]}
        line["leaves"] = {"program_grad": st.prog["grad"].tolist(),
                          "reference_grad": ref["grad"].tolist(),
                          "program_change": st.prog["change"].tolist(),
                          "reference_change": ref["change"].tolist()}
        print(json.dumps(line), flush=True)
        lines.append(line)
    summary = {}
    for name in ("loss_gap", "grad_gap", "update_gap", "grad_diff"):
        summary[name] = {
            "program_max": max(x["program"][name] for x in lines),
            "program_min": min(x["program"][name] for x in lines),
            "half_batch_min": min(x["half_batch"][name] for x in lines)}
    summary["wrong_rows_max"] = max(x["wrong_rows"] for x in lines)
    summary["epoch_errors_max"] = max(x["epoch_errors"] for x in lines)
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
