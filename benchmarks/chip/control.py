"""The check's control and planted faults, read on the chip at a cell's
own size (not part of a benchmark run).

    python3 benchmarks/chip/control.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...] [--out <file.jsonl>]

For each seed, in one process: one run of the cell as ``run.py`` makes it
(set-up, a window of ``seconds``, the check), then the same numbers with
other sides put in the program's place, on the same recorded inputs:

* ``control``: the reference computed in bfloat16 (weights, activations,
  optimizer state), the nearest precision below the float32 the
  configuration states;
* ``half_batch``: the reference's SGD step on half of each batch, the mean
  taken over that half;
* ``state_unchanged``: a step that returns its state unchanged;
* ``answer_altered``: every label and served class moved to the next class.

Each side is judged as the harness judges the program: its numbers, with
the program's own in place of those the side does not change, against the
configuration's limits. One JSON line per seed goes to standard output (and
to ``--out``): every side's readings and its ``correct``, and under
``median_leaf`` the median leaf's gaps of ``sgd_grad`` and ``sgd_update``
for the program and each side that trains (not compared; they show whether
the worst leaf is noise of one small leaf).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "..", "..", "src")]


def side_readings(truth, ctrl, s_params, rec, config):
    """Readings of the control and of each planted fault, and the median
    leaf's training gaps of each side that trains and of the program."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import check

    first = truth.first_steps()
    lab_ref, srv_ref = truth.label_logits(), truth.serve_logits()
    labels = [lab for _, lab in rec["labeled"]]
    preds = [p for *_, p in rec["served"]]
    n_cls = config["student"]["num_classes"]

    out = {}
    trained = {"program": rec["first"]}
    c_first = trained["control"] = ctrl.first_steps()
    out["control"] = check.training_gaps(first, s_params, c_first)
    out["control"]["label_gap"] = check.answer_gap(
        lab_ref, [z.argmax(-1) for z in ctrl.label_logits()])
    out["control"]["serve_gap"] = check.answer_gap(
        srv_ref, [z.argmax(-1) for z in ctrl.serve_logits()])

    half = trained["half_batch"] = truth.first_steps(half_batch=True)
    out["half_batch"] = check.training_gaps(first, s_params, half)

    # A step that returns its state unchanged: every loss is taken at the
    # starting weights, the momentum stays zero, the weights do not move.
    still = {"loss": [], "mom1": jax.tree_util.tree_map(
        lambda a: np.zeros(a.shape, np.float32), s_params),
        "params3": jax.device_get(s_params)}
    zero = jax.tree_util.tree_map(jnp.zeros_like, s_params)
    for x, y in rec["steps"].get(0, [])[:3]:
        still["loss"].append(float(truth.student.step(
            truth.lr, s_params, zero, x, y)[2]))
    out["state_unchanged"] = check.training_gaps(first, s_params, still)
    out["median_leaf"] = {
        side: {k: float(np.median(r)) for k, r in
               check.training_ratios(first, s_params, got).items()}
        for side, got in trained.items()}

    out["answer_altered"] = {
        "label_gap": check.answer_gap(lab_ref, [(a + 1) % n_cls
                                                for a in labels]),
        "serve_gap": check.answer_gap(srv_ref, [(a + 1) % n_cls
                                                for a in preds])}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    import jax

    import check
    import harness

    cell = harness.load_cell(args.workload)
    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={jax.device_count()}", flush=True)
    if dev.platform != "tpu":
        raise SystemExit("control: no TPU")
    harness.env_setup()
    t_start = T_START
    for seed in args.seeds:
        out = harness.run_cell(cell, seed, args.seconds, False, t_start,
                               device=dev)
        s_params, t_params = out["weights"]
        rec = out["recorded"]
        truth = check.Truth(cell.config, out["lr"], s_params, t_params, rec)
        ctrl = check.Truth(cell.config, out["lr"], s_params, t_params, rec,
                           precision="bfloat16")
        sides = side_readings(truth, ctrl, s_params, rec, cell.config)
        line = {"workload": cell.name, "seed": seed,
                "limits": out["limits"],
                "median_leaf": sides.pop("median_leaf"),
                "memory_peak_bytes": out["memory_peak"],
                "program": {**out["readings"], "correct": out["correct"]}}
        for side, got in sides.items():
            got = {**out["readings"], **got}
            line[side] = {**got,
                          "correct": check.correct(got, out["limits"])}
        text = json.dumps(line)
        print(text, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(text + "\n")
        del out, rec, truth, ctrl
        t_start = time.perf_counter()


if __name__ == "__main__":
    main()
