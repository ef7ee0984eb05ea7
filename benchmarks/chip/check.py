"""How ``correct`` is decided: the plain reference against what the timed
path produced.

Three kinds of numbers, each with a limit in ``configs/<config>.json``:

* training (the first three SGD steps of lane 0, which set-up drives
  through the window's own call): ``sgd_loss``, the largest relative gap of
  a step's loss; ``sgd_grad``, the worst leaf's norm of the difference
  between the first gradients (the momentum after one step) of program and
  reference; ``sgd_update``, the worst leaf's norm of the difference
  between the parameters' changes over the three steps. Vectors, not their
  norms, are compared: a gradient from half the batch has about the norm of
  the whole batch's but points elsewhere. A leaf's gap is measured against
  the larger of the reference's norm of that leaf and of the median leaf.
  Leaves whose first gradient in the reference is under a thousandth of the
  median leaf's move by round-off alone and are left out of
  ``sgd_update``.
* labels: ``label_gap``, the widest gap by which the reference teacher's
  logit of a label the window produced lies below its best logit, over a
  seeded sample of the labeled frames.
* serving: ``serve_gap``, the same for the student's served classes. The
  reference follows the run: it replays every SGD batch of the lane from
  the seeded starting weights, in order, and scores each sampled frame with
  its own weights at the version that served it.

The reference takes the weights the benchmark made from the seed and the
inputs the run recorded (frames and batches); it quantizes its own serving
copies at the precisions the configuration states.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import reference

MOVING = 1e-3  # a leaf moves when its first gradient is >= this x median


def leaf_gaps(got, want, base=None) -> Tuple[List[float], List[float]]:
    """Per leaf, in float64: the norm of ``got - want``, and the norm of
    ``want - base`` (of ``want`` where ``base`` is None)."""
    gaps, norms = [], []
    want_leaves = jax.tree_util.tree_leaves(want)
    base_leaves = (jax.tree_util.tree_leaves(base) if base is not None
                   else [0.0] * len(want_leaves))
    for g, w, b in zip(jax.tree_util.tree_leaves(got), want_leaves,
                       base_leaves):
        w = np.asarray(w, np.float64)
        gaps.append(float(np.linalg.norm(np.asarray(g, np.float64) - w)))
        norms.append(float(np.linalg.norm(w - np.asarray(b, np.float64))))
    return gaps, norms


def leaf_ratios(gaps: Sequence[float], norms: Sequence[float],
                keep: Optional[Sequence[bool]] = None) -> List[float]:
    """Each kept leaf's gap over the larger of its norm and the median kept
    leaf's norm."""
    keep = [True] * len(norms) if keep is None else keep
    base = [n for n, k in zip(norms, keep) if k]
    if not base:
        return []
    med = float(np.median(base))
    return [d / max(n, med, 1e-30)
            for d, n, k in zip(gaps, norms, keep) if k]


class Truth:
    """The reference's view of one run, at one precision."""

    def __init__(self, config: Dict[str, Any], lr: float, s_params, t_params,
                 rec: Dict[str, Any], precision: str = "highest"):
        self.config, self.lr = config, lr
        self.precision = precision
        self.student = reference.Reference(config["student"], precision)
        self.teacher = reference.Reference(config["teacher"], precision)
        self.policy = config["precision_policy"]
        self.s_params, self.t_params = s_params, t_params
        self.rec = rec

    # ------------------------------------------------------------ training
    def first_steps(self, half_batch: bool = False) -> Dict[str, Any]:
        """The first three steps of lane 0 from the seeded weights."""
        p = self.s_params
        m = jax.tree_util.tree_map(jnp.zeros_like, p)
        out: Dict[str, Any] = {"loss": []}
        for i, (x, y) in enumerate(self.rec["steps"].get(0, [])[:3]):
            if half_batch:
                x, y = x[: len(x) // 2], y[: len(y) // 2]
            p, m, loss, grads = self.student.step(self.lr, p, m, x, y)
            out["loss"].append(float(loss))
            if i == 0:
                out["mom1"] = jax.device_get(m)
                out["grad1"] = jax.device_get(grads)
        out["params3"] = jax.device_get(p)
        return out

    # ------------------------------------------------------------- answers
    def label_logits(self) -> List[np.ndarray]:
        tq = reference.mx_fake_quant(reference.cast(self.t_params,
                                                    self.precision),
                                     self.policy["labeling"])
        return [self.teacher.forward(tq, x) for x, _ in self.rec["labeled"]]

    def serve_logits(self) -> List[np.ndarray]:
        """Logits of every sampled served frame, with the weights the
        reference reaches after the same number of the lane's batches."""
        served = self.rec["served"]
        wanted = defaultdict(list)
        for i, (lane, version, _, _) in enumerate(served):
            wanted[lane].append((version, i))
        out: List[Optional[np.ndarray]] = [None] * len(served)
        for lane, items in wanted.items():
            steps = self.rec["steps"].get(lane, [])
            p = reference.cast(self.s_params, self.precision)
            m = jax.tree_util.tree_map(jnp.zeros_like, p)
            done = 0
            for version, i in sorted(items):
                while done < version:
                    p, m, _, _ = self.student.step(self.lr, p, m,
                                                   *steps[done])
                    done += 1
                q = reference.mx_fake_quant(p, self.policy["inference"])
                out[i] = self.student.forward(q, served[i][2])
        return out


def answer_gap(ref_logits: Sequence[np.ndarray],
               answers: Sequence[np.ndarray]) -> float:
    """Widest gap between the reference's best logit and its logit of the
    answer given; infinite where there is nothing to compare."""
    gaps = [z.max(-1) - z[np.arange(len(a)), np.asarray(a)]
            for z, a in zip(ref_logits, answers) if len(a)]
    return float(np.concatenate(gaps).max()) if gaps else float("inf")


def training_ratios(truth_first: Dict[str, Any], s_params,
                    got: Dict[str, Any]) -> Dict[str, List[float]]:
    """Per leaf, the gaps of a side's first gradient (``mom1``) and of its
    change over three steps (``params3``, moving leaves only) against the
    reference's, as :func:`leaf_ratios` gives them."""
    grad_gap, grad_ref = leaf_gaps(got["mom1"], truth_first["grad1"])
    med = float(np.median(grad_ref))
    moving = [g >= MOVING * med for g in grad_ref]
    upd_gap, upd_ref = leaf_gaps(got["params3"], truth_first["params3"],
                                 s_params)
    return {"sgd_grad": leaf_ratios(grad_gap, grad_ref),
            "sgd_update": leaf_ratios(upd_gap, upd_ref, moving)}


def training_gaps(truth_first: Dict[str, Any], s_params,
                  got: Dict[str, Any]) -> Dict[str, float]:
    """Gaps of a side's first three steps (``loss``, ``mom1``,
    ``params3``) against the reference's: the worst step's loss, and the
    worst leaf of :func:`training_ratios`."""
    want_loss = truth_first["loss"]
    if len(got.get("loss", [])) < 3 or len(want_loss) < 3:
        inf = float("inf")
        return {"sgd_loss": inf, "sgd_grad": inf, "sgd_update": inf}
    out = {"sgd_loss": max(abs(g - w) / abs(w)
                           for g, w in zip(got["loss"], want_loss))}
    for k, ratios in training_ratios(truth_first, s_params, got).items():
        out[k] = max(ratios, default=float("inf"))
    return out


def correct(readings: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every number within its limit; a number missing or not finite is
    not."""
    return all(readings.get(k, float("inf")) <= v for k, v in limits.items())


def readings(config: Dict[str, Any], lr: float, s_params, t_params,
             rec: Dict[str, Any]) -> Dict[str, float]:
    """Every number compared, for the program's own run."""
    truth = Truth(config, lr, s_params, t_params, rec)
    out = training_gaps(truth.first_steps(), s_params, rec["first"])
    out["label_gap"] = answer_gap(truth.label_logits(),
                                  [lab for _, lab in rec["labeled"]])
    out["serve_gap"] = answer_gap(truth.serve_logits(),
                                  [pred for *_, pred in rec["served"]])
    return out
