"""The three concurrent CL kernels as first-class objects (paper Fig. 4).

Each kernel owns its model apply (jitted once per kernel), its MX precision
handling, its virtual-clock cost on the performance estimator, and — when a
multi-device mesh is available — its sub-accelerator placement from a
``SpatialPartition``:

* ``InferenceKernel``  — student, every frame, B-SA;
* ``LabelingKernel``   — teacher pseudo-labels on sampled frames, T-SA;
* ``RetrainKernel``    — student SGD on the sample buffer, T-SA.

The engine (core/session.py) never touches models or estimators directly; it
executes ``AllocationDecision``s by calling kernel methods with the rows and
precisions the decision carries. On a single device the partition binding is
a no-op and the three kernels time-share — the paper's own fallback.

Entry points come in two flavors so the dispatch layer (core/dispatch.py)
can overlap T-SA and B-SA work: the ``*_async`` methods return **device
arrays** without forcing a host sync (JAX async dispatch keeps running), and
the classic host-returning methods are thin ``np.asarray`` wrappers kept for
callers outside the hot path. ``predict_batched`` fuses several frame
windows into one jitted apply; ``label_async`` optionally microbatches large
labeling bursts so each chunk starts executing while the next is staged.
Every jitted apply invocation bumps ``n_apply_calls`` (bench/test counter),
and ``h2d_bytes`` counts the bytes of the host (numpy) arrays a kernel hands
to a jitted program or to ``device_put`` — frames, SGD labels, the fleet
and labeling concatenations; device-resident arrays count nothing. The
retraining loop and the serving-copy fill run in profiler spans
(``dacapo.fit``, ``dacapo.fit.gather``, ``dacapo.fit.step``,
``dacapo.quantize``; see core/trace.py).
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from typing import List, Optional, Protocol, Sequence, Tuple, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.dacapo_pairs import VisionConfig
from repro.core import mx as mx_lib
from repro.core.partition import SpatialPartition
from repro.core.trace import span


class _CacheSlot:
    """One (tree, precision) cache line.

    ``quantized`` is the RESIDENT copy — the tree with weight leaves held
    as actual MX representations (``mx_lib.MXLeaf``: int8 mantissas +
    shared exponents, ~3.5× smaller than fp32). ``value`` memoizes the
    lazily-dequantized fake-quant fp32 tree legacy ``model.apply`` callers
    consume (bit-identical to ``quantize_tree`` on the source). The
    slot's own lock serializes the fill and the lazy dequantize for THIS
    key only — the cache-wide lock is never held across either."""

    __slots__ = ("lock", "quantized", "value")

    def __init__(self):
        self.lock = threading.Lock()
        self.quantized = None
        self.value = None


class ServingParamsCache:
    """Version-keyed cache of RESIDENT quantized serving copies.

    Quantizing a serving tree — one jitted call per weight leaf — is the
    expensive step, yet between retrain steps the source tree is the same
    immutable object (JAX never mutates arrays in place; ``fit`` returns a
    fresh tree), and the teacher tree never changes at all: before this
    cache, every labeling burst re-quantized the whole teacher from
    scratch. Entries key on (source-tree identity, precision); the entry
    holds a strong reference to the source tree, pinning its ``id`` for
    the entry's lifetime, which makes identity a sound version key — a
    retrained tree is a NEW object, so its serving copy can never be
    served stale. :meth:`RetrainKernel.fit` additionally invalidates the
    tree it supersedes explicitly. ``maxsize=0`` disables caching (the
    benches' uncached baseline); eviction is LRU.

    Entries store the QUANTIZED representation (``quantize_tree_mx``), not
    a fake-quant fp32 tree: :meth:`get_quantized` hands the resident copy
    to weight-resident consumers (``ops.mx_matmul_prequant``), while
    :meth:`get` lazily dequantizes — once, memoized — for legacy apply
    paths, bit-identical to the former ``quantize_tree`` output.

    Locking: the cache-wide lock covers BOOKKEEPING ONLY (hit/miss
    counters, LRU order, slot claim/eviction) and is never held across a
    quantization. Each slot carries its own fill lock, so under
    overlapped shard stepping (``FleetManager(parallel_shards=N)``) a
    slow fill of one lane's tree no longer serializes every other lane's
    lookup; racing getters of the SAME key still produce exactly one fill
    (``fills`` counts the whole-tree quantizations actually executed).
    """

    def __init__(self, maxsize: int = 8):
        self.maxsize = maxsize
        self.hits = 0
        self.misses = 0
        self.fills = 0  # whole-tree quantizations actually executed
        self._lock = threading.RLock()
        # id(source tree) -> (source tree, {precision: _CacheSlot})
        self._entries: "OrderedDict[int, tuple]" = OrderedDict()

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def _claim(self, params, precision: str) -> _CacheSlot:
        """Return the slot for (params, precision), creating and publishing
        it on a miss — bookkeeping only, constant-time under the cache
        lock. The caller fills the slot under the slot's own lock."""
        key = id(params)
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None and entry[0] is params:
                slot = entry[1].get(precision)
                if slot is not None:
                    self.hits += 1
                    self._entries.move_to_end(key)
                    return slot
            self.misses += 1
            slot = _CacheSlot()
            if self.maxsize <= 0:
                return slot  # unpublished: the uncached baseline refills
            if entry is None or entry[0] is not params:
                entry = (params, {})
                self._entries[key] = entry
            entry[1][precision] = slot
            self._entries.move_to_end(key)
            while len(self._entries) > self.maxsize:
                self._entries.popitem(last=False)
            return slot

    def _count_fill(self) -> None:
        with self._lock:
            self.fills += 1

    def get(self, params, precision: str, quantize=None):
        """The fake-quant fp32 serving tree for unmodified ``model.apply``
        callers. Default path: fill the resident quantized rep (once per
        key), lazily dequantize (once per key, memoized) — bit-identical
        to ``quantize_tree(params, precision)``. A custom ``quantize``
        callable stores its return value directly (test/bench hook)."""
        slot = self._claim(params, precision)
        with slot.lock:
            if slot.value is None:
                with span("quantize"):
                    if slot.quantized is None:
                        self._count_fill()
                        if quantize is not None:
                            slot.value = quantize(params, precision)
                        else:
                            slot.quantized = mx_lib.quantize_tree_mx(
                                params, precision)
                    if slot.value is None:
                        slot.value = mx_lib.dequantize_tree_mx(
                            slot.quantized)
            return slot.value

    def get_quantized(self, params, precision: str):
        """The RESIDENT copy — weight leaves as ``mx_lib.MXLeaf`` — for
        consumers that feed quantized operands straight to the kernels."""
        slot = self._claim(params, precision)
        with slot.lock:
            if slot.quantized is None:
                with span("quantize"):
                    self._count_fill()
                    slot.quantized = mx_lib.quantize_tree_mx(params,
                                                             precision)
            return slot.quantized

    def invalidate(self, params=None) -> None:
        """Drop the entries of ``params`` — or everything when ``None``."""
        with self._lock:
            if params is None:
                self._entries.clear()
                return
            entry = self._entries.get(id(params))
            if entry is not None and entry[0] is params:
                del self._entries[id(params)]

    def stats(self) -> dict:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "entries": len(self._entries)}


@runtime_checkable
class Kernel(Protocol):
    """What the engine requires of a kernel."""

    name: str
    role: str  # "t_sa" | "b_sa" — which sub-accelerator it runs on

    def bind_partition(self, partition: SpatialPartition) -> None:
        """Adopt a sub-mesh placement (no-op when time-shared)."""

    def time_per_sample(self, rows: int, precision: str) -> float:
        """Virtual-clock seconds per sample at the given row count."""


class _PlacedKernel:
    """Shared placement logic: hold this kernel's sub-mesh and, when a real
    (non-time-shared) partition is bound, run on its first device — frames
    are staged there per call, parameter trees are moved there once per
    tree version (:meth:`_place`), so a retrained student crosses to the
    B-SA once per weight update, not once per call.

    Kernels also know how to read a resolved
    :class:`~repro.core.decision.SpatialPlan`: each kernel picks its own
    rows (by ``role``) and precision (by ``precision_field``) off the
    plane, so the engine never unpacks rows/precisions itself — the
    ``plan_*`` entry points below are the spatial-plane view of the classic
    ``time_per_sample``-style cost methods.
    """

    role = "t_sa"
    precision_field = "retraining"  # which PrecisionPolicy field this reads

    _PLACED_MAX = 8  # parameter-tree versions kept on the device (LRU)

    def __init__(self):
        self.submesh = None
        self._device = None
        # id(source tree) -> (source tree, copy on self._device); the strong
        # reference pins the id, as in ServingParamsCache.
        self._placed: "OrderedDict[int, tuple]" = OrderedDict()
        self.n_apply_calls = 0  # jitted-dispatch counter (bench/tests)
        self.h2d_bytes = 0  # bytes of host arrays handed to the device

    # --------------------------------------------------- spatial-plane view
    def plan_rows(self, spatial, role: Optional[str] = None) -> int:
        """This kernel's row count on a resolved spatial plane. ``role``
        overrides the kernel's home sub-accelerator (sequential dispatch
        charges validation inference on the T-SA chain)."""
        role = role or self.role
        return spatial.rows_bsa if role == "b_sa" else spatial.rows_tsa

    def plan_precision(self, spatial) -> str:
        return getattr(spatial.precisions, self.precision_field)

    def plan_time_per_sample(self, spatial,
                             role: Optional[str] = None) -> float:
        """Virtual-clock seconds per sample at the plane's rows/precision."""
        return self.time_per_sample(self.plan_rows(spatial, role),
                                    self.plan_precision(spatial))

    def bind_partition(self, partition: SpatialPartition) -> None:
        self._placed.clear()
        if partition.time_shared:
            self.submesh, self._device = None, None
            return
        self.submesh = partition.b_sa if self.role == "b_sa" else partition.t_sa
        self._device = (None if self.submesh is None
                        else self.submesh.devices.flat[0])

    def _put(self, x):
        return x if self._device is None else jax.device_put(x, self._device)

    def _count_h2d(self, *arrays) -> None:
        """Add the host (numpy) arrays among ``arrays`` to ``h2d_bytes``."""
        self.h2d_bytes += sum(a.nbytes for a in arrays
                              if isinstance(a, np.ndarray))

    def _place(self, tree):
        """``tree`` on this kernel's device, moved once per tree version:
        JAX arrays are immutable, so the source tree's identity is its
        version and a repeat call is a lookup."""
        if self._device is None:
            return tree
        key = id(tree)
        entry = self._placed.get(key)
        if entry is not None and entry[0] is tree:
            self._placed.move_to_end(key)
            return entry[1]
        placed = jax.device_put(tree, self._device)
        self._placed[key] = (tree, placed)
        while len(self._placed) > self._PLACED_MAX:
            self._placed.popitem(last=False)
        return placed

    def _run_apply(self, params, x):
        self.n_apply_calls += 1
        self._count_h2d(x)
        return self._apply(self._place(params), self._put(x))


class InferenceKernel(_PlacedKernel):
    """Student inference on the B-SA: serves every frame, scores accuracy."""

    name = "inference"
    role = "b_sa"
    precision_field = "inference"

    def __init__(self, model, full_cfg: VisionConfig, estimator,
                 apply_mx: bool):
        super().__init__()
        self.model = model
        self.full_cfg = full_cfg
        self.estimator = estimator
        self.apply_mx = apply_mx
        self._apply = jax.jit(model.apply)
        self._apply_fleet = None  # lazily-built vmapped multi-lane apply
        self.serving_cache = ServingParamsCache()

    def serving_params(self, params, precision: str):
        """UpdateWeight (Alg. 1 line 6): the serving copy at the inference
        precision; the retraining master stays fp32. Served from the
        version-keyed :class:`ServingParamsCache`, which keeps the tree
        RESIDENT in quantized form — re-requesting the serving copy of an
        unchanged tree is a hit, not a re-quantize, and the fp32 view the
        apply consumes is dequantized lazily exactly once per version."""
        if self.apply_mx:
            return self.serving_cache.get(params, precision)
        return params

    def serving_quantized(self, params, precision: str):
        """The RESIDENT quantized serving copy (weight leaves as
        ``mx_lib.MXLeaf``) — for weight-resident consumers that feed
        ``ops.mx_matmul_prequant`` directly instead of ``model.apply``."""
        if self.apply_mx:
            return self.serving_cache.get_quantized(params, precision)
        return params

    def predict_async(self, params, x) -> jax.Array:
        """Class ids as a device array — no host sync; the dispatch layer
        collects when (and if) feedback needs the values."""
        return jnp.argmax(self._run_apply(params, x), -1)

    def predict(self, params, x) -> np.ndarray:
        return np.asarray(self.predict_async(params, x))

    def predict_batched(self, params,
                        windows: Sequence[np.ndarray]) -> List[jax.Array]:
        """Fuse several frame windows into ONE jitted apply.

        The seed path issued one jitted call per score window; fusing
        concatenates the windows on the batch axis, applies once, and splits
        the predictions back per window (device-side slices, still async).
        Per-sample models (GroupNorm, no cross-batch stats) make the fused
        predictions equal to the per-window ones.
        """
        if not windows:
            return []
        if len(windows) == 1:
            return [self.predict_async(params, windows[0])]
        sizes = [len(w) for w in windows]
        fused = self.predict_async(params, np.concatenate(windows, axis=0))
        out, off = [], 0
        for size in sizes:
            out.append(fused[off: off + size])
            off += size
        return out

    def predict_fleet_async(self, params_list: Sequence,
                            windows: Sequence[np.ndarray]
                            ) -> List[jax.Array]:
        """Serve several lanes' frame windows in ONE device program — the
        B-SA mirror of :meth:`LabelingKernel.label_fleet_async`.

        Each fleet lane serves its own (quantized) student tree, so a
        single fused batch is not enough: the per-lane trees are stacked on
        a new leading axis, the windows zero-padded to the longest lane and
        stacked likewise, and one jitted ``vmap``-ped apply serves the
        whole fleet; per-lane predictions split back out as device-side
        slices (still async), pad rows dropped. A single lane takes the
        exact ``predict_async`` path. Note the vmapped apply may differ
        from per-lane applies in float ulps (different XLA lowering), which
        is why fleet batched serving is an opt-in knob — see
        ``FleetSpec.serve_batched``."""
        if not windows:
            return []
        if len(windows) == 1:
            return [self.predict_async(params_list[0], windows[0])]
        sizes = [len(w) for w in windows]
        n_max = max(sizes)
        padded = np.stack([
            w if len(w) == n_max else np.concatenate(
                [w, np.zeros((n_max - len(w),) + w.shape[1:], w.dtype)])
            for w in windows])
        stacked = jax.tree_util.tree_map(
            lambda *leaves: jnp.stack(leaves),
            *[self._place(p) for p in params_list])
        if self._apply_fleet is None:
            self._apply_fleet = jax.jit(jax.vmap(self.model.apply))
        self.n_apply_calls += 1
        self._count_h2d(padded)
        logits = self._apply_fleet(stacked, self._put(padded))
        preds = jnp.argmax(logits, -1)
        return [preds[i, :size] for i, size in enumerate(sizes)]

    def time_per_sample(self, rows: int, precision: str) -> float:
        return self.estimator.forward_time(self.full_cfg, rows, precision,
                                           batch=1)

    def fps(self, rows: int, precision: str) -> float:
        return self.estimator.inference_fps(self.full_cfg, rows, precision)

    def keep_frac(self, rows: int, precision: str,
                  target_fps: float) -> float:
        """Fraction of stream frames the B-SA sustains (paper Fig. 2)."""
        return min(1.0, self.fps(rows, precision) / target_fps)

    def plan_keep_frac(self, spatial, target_fps: float) -> float:
        """Sustainable frame fraction at the spatial plane's B-SA rows and
        serving precision."""
        return self.keep_frac(spatial.rows_bsa, spatial.precisions.inference,
                              target_fps)


class LabelingKernel(_PlacedKernel):
    """Teacher pseudo-labeling on the T-SA (time-shared with retraining)."""

    name = "labeling"
    role = "t_sa"
    precision_field = "labeling"

    def __init__(self, model, full_cfg: VisionConfig, estimator,
                 apply_mx: bool):
        super().__init__()
        self.model = model
        self.full_cfg = full_cfg
        self.estimator = estimator
        self.apply_mx = apply_mx
        self._apply = jax.jit(model.apply)
        self.serving_cache = ServingParamsCache()

    def label_async(self, params, x, precision: str,
                    microbatch: Optional[int] = None) -> jax.Array:
        """Pseudo-labels as a device array (no host sync). With
        ``microbatch``, large labeling bursts (N_ldd on drift) are split into
        chunks so each starts executing on the T-SA while the next is staged
        — per-sample models make the result equal to one full-batch call.
        The teacher's serving copy comes from the version-keyed cache,
        which holds it RESIDENT in quantized form: the tree never changes,
        so every burst after the first is a hit on the already-dequantized
        view instead of a whole-tree re-quantize."""
        if self.apply_mx:
            params = self.serving_cache.get(params, precision)
        if microbatch and len(x) > microbatch:
            parts = [jnp.argmax(self._run_apply(params, x[i: i + microbatch]),
                                -1)
                     for i in range(0, len(x), microbatch)]
            return jnp.concatenate(parts)
        return jnp.argmax(self._run_apply(params, x), -1)

    def label(self, params, x, precision: str,
              microbatch: Optional[int] = None) -> np.ndarray:
        return np.asarray(self.label_async(params, x, precision, microbatch))

    def serving_quantized(self, params, precision: str):
        """The teacher's RESIDENT quantized copy (see
        :meth:`InferenceKernel.serving_quantized`)."""
        if self.apply_mx:
            return self.serving_cache.get_quantized(params, precision)
        return params

    def label_fleet_async(self, params, bursts: Sequence[np.ndarray],
                          precision: str,
                          microbatch: Optional[int] = None
                          ) -> List[jax.Array]:
        """Label several streams' bursts in ONE pass over the shared T-SA.

        The fleet's labeling work arrives as one burst per camera stream;
        issuing them separately would microbatch each burst on its own
        (``sum(ceil(n_i / mb))`` jitted calls and N tail fragments).
        Batching concatenates the bursts on the batch axis, microbatches the
        *combined* burst (``ceil(sum(n_i) / mb)`` calls — chunks freely
        cross stream boundaries), and splits the labels back per stream as
        device-side slices, still async. Per-sample models make the result
        equal to labeling each burst alone; a single-burst fleet takes the
        exact ``label_async`` path the single-stream goldens pin."""
        bursts = [b for b in bursts]
        if not bursts:
            return []
        if len(bursts) == 1:
            return [self.label_async(params, bursts[0], precision,
                                     microbatch)]
        sizes = [len(b) for b in bursts]
        fused = self.label_async(params, np.concatenate(bursts, axis=0),
                                 precision, microbatch)
        out, off = [], 0
        for size in sizes:
            out.append(fused[off: off + size])
            off += size
        return out

    def time_per_sample(self, rows: int, precision: str) -> float:
        return self.estimator.forward_time(self.full_cfg, rows, precision,
                                           batch=1)


class RetrainKernel(_PlacedKernel):
    """Student SGD-with-momentum retraining on the T-SA."""

    name = "retraining"
    role = "t_sa"
    precision_field = "retraining"

    def __init__(self, model, full_cfg: VisionConfig, estimator, hp):
        super().__init__()
        self.model = model
        self.full_cfg = full_cfg
        self.estimator = estimator
        self.hp = hp
        self._step = jax.jit(self._sgd_step)
        self.last_loss = None  # loss of the last SGD step, on the device
        # Serving caches to invalidate when retraining supersedes a tree
        # (the session wires the inference kernel's cache in here).
        self.invalidates: Tuple[ServingParamsCache, ...] = ()

    def _sgd_step(self, params, opt, x, y):
        def loss_fn(p):
            logits = self.model.apply(p, x)
            logp = jax.nn.log_softmax(logits)
            return -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        new_opt = jax.tree_util.tree_map(
            lambda m, g: 0.9 * m + g, opt, grads)
        new_params = jax.tree_util.tree_map(
            lambda p, m: p - self.hp.lr * m, params, new_opt)
        return new_params, new_opt, loss

    def init_state(self, params):
        return jax.tree_util.tree_map(jnp.zeros_like, params)

    def fit(self, params, opt, xt: np.ndarray, yt: np.ndarray,
            rng: np.random.Generator,
            epochs: Optional[int] = None) -> Tuple[object, object, int]:
        """Retrain (Alg. 1 line 5): epochs x minibatch SGD over D_t.
        Returns (params, opt, n_batches) — the engine charges
        n_batches * time_per_batch to the virtual clock, and n_batches is
        exactly the number of SGD steps executed (a D_t smaller than one
        SGD batch runs — and charges — zero steps). ``epochs`` overrides
        the hyper-parameter default — the knob cross-stream allocators use
        to proportion retraining depth per stream. Retraining supersedes
        the incoming tree: its cached serving copies are invalidated on
        every registered :class:`ServingParamsCache` (identity keys make
        stale hits impossible anyway — this reclaims the entries)."""
        for cache in self.invalidates:
            cache.invalidate(params)
        with span("fit"):
            # Master weights and optimizer state live on the T-SA device: a
            # no-op once they are there (every fit after the first).
            params, opt = self._put(params), self._put(opt)
            hp = self.hp
            n_batches = 0
            for _ in range(epochs if epochs is not None else hp.epochs):
                perm = rng.permutation(len(xt))
                for i in range(0, len(xt) - hp.sgd_batch + 1, hp.sgd_batch):
                    with span("fit.gather"):
                        idx = perm[i: i + hp.sgd_batch]
                        xb, yb = xt[idx], yt[idx]
                        self._count_h2d(xb, yb)
                        xb, yb = self._put(xb), self._put(yb)
                    with span("fit.step"):
                        params, opt, self.last_loss = self._step(
                            params, opt, xb, yb)
                    n_batches += 1
                    self.n_apply_calls += 1
        return params, opt, n_batches

    def time_per_batch(self, rows: int, precision: str) -> float:
        return self.estimator.train_step_time(self.full_cfg, rows, precision,
                                              self.hp.sgd_batch)

    def plan_time_per_batch(self, spatial) -> float:
        """SGD-batch cost at the plane's T-SA rows/retraining precision."""
        return self.time_per_batch(spatial.rows_tsa,
                                   spatial.precisions.retraining)

    def time_per_sample(self, rows: int, precision: str) -> float:
        return self.time_per_batch(rows, precision) / self.hp.sgd_batch
