"""Shared trainer for the CTR model family (FM / FFM / NFM / Wide&Deep).

Replaces the reference's per-model Train()/batchGradCompute/ApplyGrad loops
(e.g. ``train_fm_algo.cpp:35-133``): where the reference shards rows across a
thread pool and accumulates into a shared grad buffer (Hogwild-style), here
one jitted SPMD step computes the batched gradient and the optimizer update;
data parallelism is a mesh axis, not threads — the grad all-reduce that the
reference implements by hand over ZeroMQ rings (ring_collect.h:48-72) is the
``psum`` XLA inserts for sharded-batch gradients.

The reference trains FM full-batch (``__global_minibatch_size = dataRow_cnt``,
train_fm_algo.cpp:38) with one Adagrad step per epoch; ``batch_size=None``
reproduces that, an integer gives minibatch SGD (the DL-family default).
"""

from __future__ import annotations

import logging
import time
from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from lightctr_tpu import obs
from lightctr_tpu import optim as optim_lib
from lightctr_tpu.obs import device as device_mod
from lightctr_tpu.obs import health as health_mod
from lightctr_tpu.obs import quality as quality_mod
from lightctr_tpu.obs import resources as resources_mod
from lightctr_tpu.obs import stepwatch as stepwatch_mod
from lightctr_tpu.obs import trace as trace_mod
from lightctr_tpu.utils.profiling import annotate
from lightctr_tpu.core.config import TrainConfig
from lightctr_tpu.core.mesh import replicated, shard_batch
from lightctr_tpu.data import ingest as ingest_mod
from lightctr_tpu.data.batching import minibatches
from lightctr_tpu.models._common import tree_copy
from lightctr_tpu.ops import losses as losses_lib
from lightctr_tpu.ops import metrics as metrics_lib
from lightctr_tpu.ops.activations import sigmoid

from lightctr_tpu.obs import ensure_console_logging

_LOG = logging.getLogger(__name__)


def _health_pack(loss, grad_norm):
    """One f32[2] device vector ``[loss, grad_norm]`` — the head of the
    health feed's single-fetch payload (see ``CTRTrainer._feed_health``);
    a step appends what else it reports (the sparse trainer's counts or
    overflow slot), then the quality sketch."""
    return jnp.stack([
        jnp.asarray(loss, jnp.float32), jnp.asarray(grad_norm, jnp.float32)
    ])


#: what ``TrainConfig.loss`` may say
LOSSES = ("logistic", "softmax_xent")
#: the integers the softmax loss counts itself, ahead of the model's own
#: (``logits_fn.step_counts``): positions, positions with a target, and
#: packed documents, of one step's batch
SEQ_COUNTS = ("trainer_seq_tokens_total", "trainer_seq_targets_total",
              "trainer_seq_documents_total")


def softmax_count_names(logits_fn) -> tuple:
    """``((name, labels), ...)`` of the int32 vector a softmax loss
    function returns beside the loss: :data:`SEQ_COUNTS`, then what the
    model's ``logits_fn.step_counts`` names (``models/kimi_linear.py``:
    the expert layers' assignments)."""
    return (tuple((name, {}) for name in SEQ_COUNTS)
            + tuple(getattr(logits_fn, "step_counts", ())))


class CompressedRingState(NamedTuple):
    """Optimizer state of the wire-compressed data-parallel path: the inner
    optax state (replicated) plus the per-replica EF-SGD residual carry
    ([n_devices, padded_grad_len], sharded over ``data``) — each replica's
    quantization error re-enters its next encode, so the int8 codec's bias
    becomes a delayed contribution instead of a loss (how the reference's
    fully-coded ring wire still lands ~1.0 accuracy, 4_node_ring.png)."""

    inner: Any
    residual: jax.Array


class CTRTrainer:
    """Binary-CTR trainer over a ``logits(params, batch)`` function.

    Parameters
    ----------
    params: initial parameter pytree.
    logits_fn: (params, batch) -> [B] raw scores (pre-sigmoid).
    l2_fn: optional (params, batch) -> scalar penalty.  MUST be extensive in
        the batch — a sum over the batch's touched features, like
        ``fm.l2_penalty`` (per-occurrence L2, train_fm_algo.cpp:108-115) —
        because it is divided by the batch size alongside the mean loss, and
        under data parallelism (sharded batches, ``compress_bits``, or
        ``zero_sharded``) each replica contributes its local sum.  A batch-independent whole-table
        norm would be over-counted n_devices-fold in the compressed path.
    fused_fn: optional (params, batch) -> (logits, l2) computing both from
        one set of gathers (e.g. fm.logits_with_l2); takes precedence over
        (logits_fn-for-training, l2_fn).
    optimizer: any optax transform; defaults to Adagrad at cfg.learning_rate
        (the reference FM family's workhorse, gradientUpdater.h:127-154).
    mesh: optional Mesh for data-parallel execution; batches are sharded over
        the ``data`` axis, params replicated unless ``param_shardings`` says
        otherwise.
    param_shardings: optional pytree of NamedSharding matching ``params`` —
        e.g. embedding tables row-sharded over the ``embed`` axis (the PS
        layout); optimizer state inherits the same shardings.
    compress_bits: when set (8 or 16) with a mesh, the data-parallel gradient
        exchange runs as an explicit ring all-reduce whose every hop is
        quantile-compressed to that width — the production wiring of the
        reference's compress-all-wire-traffic policy (fp16 on every PS value,
        paramserver.h:161-163; int8 QuantileCompress, README.md:60).  The
        optimizer then applies the identical decoded mean gradient on every
        replica.
    compress_range: symmetric quantization range; must bound a single
        device's gradient magnitudes (inputs are pre-divided by the ring size
        so partial sums stay inside it).  The string ``"dynamic"`` measures
        the range per call (one ring-global scalar pmax) so the codec tracks
        the gradient scale through training.
    compress_mode: quantile-table shape ("uniform" / "normal" / "log",
        ops/quantize.py).  Default: "normal" for ``compress_bits <= 8``
        (resolution concentrated where gradients live — the measured best
        int8 table), "uniform" for 16-bit (already parity-grade).
        Independent of ``error_feedback``.
    error_feedback: carry each replica's quantization error into its next
        encode (EF-SGD).  Default: on for ``compress_bits <= 8`` (where the
        codec bias is material), off for 16-bit.  The residual lives in the
        optimizer state (``CompressedRingState``), so scan/fit paths thread
        it automatically.
    zero_sharded: cross-replica weight-update sharding (Xu et al. 2020,
        arXiv:2004.13336 — the ZeRO-1 idea as XLA expresses it): instead of
        every replica applying the identical full-size optimizer update, the
        gradient is reduce-scattered over the ``data`` axis, each replica
        updates only its 1/n shard of the flattened parameters with its 1/n
        shard of optimizer state, and the new parameters are all-gathered.
        Same trajectory as replicated data-parallel (tested); optimizer
        state memory drops to 1/n per device and the update FLOPs shard
        with it.
    """

    def __init__(
        self,
        params,
        logits_fn: Callable,
        cfg: TrainConfig,
        l2_fn: Optional[Callable] = None,
        optimizer: Optional[optax.GradientTransformation] = None,
        mesh=None,
        fused_fn: Optional[Callable] = None,
        param_shardings=None,
        compress_bits: Optional[int] = None,
        compress_range: float | str = 1.0,
        compress_mode: Optional[str] = None,
        error_feedback: Optional[bool] = None,
        zero_sharded: bool = False,
        quality_bins: Optional[int] = None,
        resources: Optional[bool] = None,
        device: Optional[bool] = None,
    ):
        self.cfg = cfg
        if cfg.loss not in LOSSES:
            raise ValueError(f"TrainConfig.loss must be one of {LOSSES}, "
                             f"got {cfg.loss!r}")
        self.logits_fn = logits_fn
        self.l2_fn = l2_fn
        self.fused_fn = fused_fn
        self.tx = optimizer or optim_lib.adagrad(cfg.learning_rate)
        self.mesh = mesh
        self.compress_bits = compress_bits
        self.compress_range = compress_range
        self.zero_sharded = zero_sharded
        if zero_sharded:
            if mesh is None:
                raise ValueError("zero_sharded requires a mesh (it shards the "
                                 "update over the data axis)")
            if param_shardings is not None or compress_bits is not None:
                raise ValueError(
                    "zero_sharded composes with replicated params and the "
                    "plain optax path only"
                )
        if param_shardings is not None and mesh is None:
            raise ValueError("param_shardings requires a mesh")
        if compress_bits is not None:
            if mesh is None:
                raise ValueError("compress_bits requires a mesh (it compresses "
                                 "the cross-device gradient exchange)")
            if param_shardings is not None:
                raise ValueError("compress_bits assumes replicated params "
                                 "(ring-exchanged data-parallel gradients)")
        self.error_feedback = (
            error_feedback if error_feedback is not None
            else (compress_bits is not None and compress_bits <= 8)
        )
        if error_feedback and compress_bits is None:
            raise ValueError("error_feedback rides the compressed ring; set "
                             "compress_bits")
        if isinstance(compress_range, str) and compress_range != "dynamic":
            raise ValueError(
                f"compress_range must be a float or 'dynamic', "
                f"got {compress_range!r}"
            )
        self.compress_mode = (
            compress_mode if compress_mode is not None
            else ("normal" if (compress_bits is not None
                               and compress_bits <= 8) else "uniform")
        )
        self._param_sharding = (
            param_shardings if param_shardings is not None else
            (replicated(mesh) if mesh is not None else None)
        )
        # the state as the step reads and writes it; ``params`` and
        # ``opt_state`` are its surface (a subclass may keep leaves in
        # another layout than it shows)
        self._params = self._own(params)
        if zero_sharded or compress_bits is not None:
            # both flows flatten the params and pad to a multiple of the
            # ring size; the compressed ring covers only the leaves
            # _ring_tree keeps on it (hybrid subclasses exchange table
            # leaves through the sparse path instead)
            from jax.flatten_util import ravel_pytree

            n = mesh.shape["data"]
            if zero_sharded:
                flat, unravel = ravel_pytree(self._params)
                self._zero_unravel = unravel
                self._zero_len = flat.shape[0]
                self._zero_pad = ((flat.shape[0] + n - 1) // n) * n
            else:
                flat, _ = ravel_pytree(self._ring_tree(self._params))
                self._ring_pad = ((flat.shape[0] + n - 1) // n) * n
        # live telemetry sink for step/exchange metrics; reassign before
        # training to isolate a run (benches give each trainer a fresh
        # MetricsRegistry)
        self.telemetry = obs.default_registry()
        # training-dynamics health: per-step loss + gradient global norm
        # (the in-jit scalar every step variant returns) feed the process
        # monitor; reassign ``self.health`` (or None) to isolate/disable
        self.health = health_mod.default_monitor()
        health_mod.ensure_trainer_detectors(self.health)
        # (loss, grad_norm) device scalars of recent steps, oldest first:
        # the health feed drains the ones ALREADY materialized
        # (jax.Array.is_ready) — fetching the in-flight step's values
        # would force a device sync per step and stall the dispatch
        # pipeline (the <5% overhead guard measures exactly that)
        self._health_pending: list = []
        # model-quality sketch (obs/quality.py): when armed (ctor arg or
        # LIGHTCTR_QUALITY) every step variant concatenates a fixed-size
        # f32[4*bins] calibration/AUC/logloss sketch onto the health
        # vector; it rides the same is_ready drain, so arming it never
        # syncs the in-flight step.  Static at trace time: unarmed
        # trainers keep the exact PR-4 health payload.
        self._quality_bins = quality_mod.resolve_bins(quality_bins)
        if cfg.loss != "logistic" and (
                self._quality_bins is not None or zero_sharded
                or compress_bits is not None):
            raise ValueError(
                f"loss={cfg.loss!r} runs on the plain one-program step: the "
                "quality sketch scores binary labels, and the compressed "
                "ring and the sharded update build the logistic loss")
        self.quality: Optional[quality_mod.QualityTracker] = None
        if self._quality_bins is not None:
            self.quality = quality_mod.QualityTracker(
                component="trainer", num_bins=self._quality_bins,
                monitor=self.health, registry=self.telemetry,
            )
        # step stall watchdog (obs/stepwatch.py): wall time since the
        # last COMPLETED step vs an EWMA-derived deadline — the signal a
        # wedged exchange cannot suppress.  Armed by LIGHTCTR_STALL=1 (or
        # arm_stepwatch()); rides the same per-step drain as the health
        # feed and marks phases (input/exec/exchange/apply) as the step
        # moves, so a trip names where it is stuck.
        self.stepwatch = stepwatch_mod.maybe_from_env(self.health)
        # resource watch (obs/resources.py): when armed (ctor arg or
        # LIGHTCTR_RESOURCES) a per-trainer CompileTracker polls this
        # trainer's live jit cache-entry counts every few steps and feeds
        # the recompile-storm detector — a shape leak (unpadded batch
        # tails churning the ladder) becomes a /healthz trip instead of a
        # silent retrace-per-step slowdown.
        self.resources: Optional[resources_mod.CompileTracker] = None
        if resources_mod.resolve_armed(resources):
            self.resources = resources_mod.CompileTracker(
                component="trainer", registry=self.telemetry,
                monitor=self.health,
            )
        # device plane (obs/device.py): when armed (ctor arg or
        # LIGHTCTR_DEVICE) a per-trainer ProgramCatalog records the step
        # program's arg specs (cost/memory analysis reads happen at scrape
        # time, never on the step path) and a LiveBufferCensus samples
        # jax.live_arrays() with the trainer state tagged; the process
        # donation watch binds to this trainer's registry/monitor so
        # verify_donation misses trip the donation_miss detector here.
        self.device: Optional[device_mod.ProgramCatalog] = None
        self.device_census: Optional[device_mod.LiveBufferCensus] = None
        if device_mod.resolve_armed(device):
            self.device = device_mod.ProgramCatalog(
                component="trainer", registry=self.telemetry,
                monitor=self.health,
            )
            self.device_census = device_mod.LiveBufferCensus(
                registry=self.telemetry, monitor=self.health,
                name="trainer",
            )
            self.device_census.register_tag(
                "trainer_state", lambda: (self._params, self._opt_state))
            device_mod.default_donation_watch().bind(
                registry=self.telemetry, monitor=self.health)
        self._steps_seen = 0
        self._opt_state = self._init_opt_state(self._params)  # inherits shardings
        # donate (params, opt_state): the old trees are dead after each step,
        # letting XLA update in place instead of copying the tables
        self._step = jax.jit(self._build_step(), donate_argnums=(0, 1))
        self._logits_j = jax.jit(self.logits_fn)
        self._scan_cache: Dict[int, Callable] = {}
        if self.resources is not None:
            self.resources.track("trainer_step", self._step)
            self.resources.track("trainer_logits", self._logits_j)

    def _build_step(self):
        """The training step: plain (XLA inserts psum for sharded batches),
        compressed-ring data-parallel when ``compress_bits`` is set, or the
        sharded-weight-update form when ``zero_sharded`` is set.

        Every variant returns ``(params, opt_state, loss, health)`` where
        ``health`` is one f32[2] device vector ``[loss, grad_norm]``: the
        gradient GLOBAL norm is reduced to a scalar inside the jitted
        step and packed next to the loss, so the health monitor's feed
        costs a single device->host fetch (and nothing at all when
        unread — XLA dead-code-eliminates it out of the scan paths)."""
        if self.compress_bits is not None:
            return self._make_compressed_step()
        if self.zero_sharded:
            return self._make_zero_step()
        return self._make_step()

    def _ring_tree(self, params):
        """The param subtree whose gradients ride the dense (compressed)
        ring exchange — everything, by default.  Hybrid subclasses
        (Parallax's split, arXiv:1808.02621: dense variables over the ring,
        sparse variables over an index+value exchange) override this to
        exclude the leaves they exchange sparsely."""
        return params

    def _make_loss_fn(self, with_probs: bool = False):
        """``(params, batch) -> loss`` (``(loss, probs)`` with
        ``with_probs``) of the logistic loss; of ``loss="softmax_xent"``
        always ``(loss, counts)``: :meth:`_make_softmax_loss_fn`."""
        if self.cfg.loss == "softmax_xent":
            return self._make_softmax_loss_fn()
        lambda_l2 = self.cfg.lambda_l2
        l2_fn = self.l2_fn
        logits_fn = self.logits_fn
        fused_fn = self.fused_fn

        def loss_fn(params, batch):
            if fused_fn is not None:
                z, l2 = fused_fn(params, batch)
            else:
                z = logits_fn(params, batch)
                l2 = l2_fn(params, batch) if l2_fn is not None else 0.0
            n = z.shape[0]
            loss = losses_lib.logistic_loss(z, batch["labels"], reduction="sum")
            if lambda_l2 > 0.0:
                loss = loss + lambda_l2 * l2
            if with_probs:
                # aux for the quality sketch: the predicted probabilities
                # of the SAME forward pass (no second scoring pass)
                return loss / n, sigmoid(z)
            return loss / n

        return loss_fn

    def _make_softmax_loss_fn(self):
        """``(params, batch) -> (loss, counts)``: the mean, over the
        positions ``batch["target_mask"]`` marks, of the softmax
        cross-entropy of ``batch["targets"]`` under the model's ``[B, T,
        V]`` logits (plus ``lambda_l2 * l2_fn`` over their number, as the
        logistic loss adds it).  ``counts`` is the int32 vector
        :func:`softmax_count_names` names: the batch's positions, targets
        and documents (``batch["segment_ids"]`` where the batch is packed)
        and, where ``logits_fn`` returns ``(logits, counts)``, the
        model's."""
        lambda_l2, l2_fn, logits_fn = self.cfg.lambda_l2, self.l2_fn, self.logits_fn

        def loss_fn(params, batch):
            out = logits_fn(params, batch)
            z, model_counts = out if isinstance(out, tuple) else (out, None)
            with annotate("seq/head_loss"):
                mask = batch["target_mask"].astype(z.dtype)
                picked = jnp.take_along_axis(
                    z, batch["targets"][..., None], axis=-1)[..., 0]
                loss = jnp.sum((jax.nn.logsumexp(z, axis=-1) - picked) * mask)
                n = jnp.maximum(jnp.sum(mask), 1.0)
            if lambda_l2 > 0.0 and l2_fn is not None:
                loss = loss + lambda_l2 * l2_fn(params, batch)
            docs = mask.shape[0]
            if "segment_ids" in batch:
                seg = batch["segment_ids"]
                docs = docs + jnp.sum(seg[:, 1:] != seg[:, :-1])
            counts = jnp.stack([jnp.int32(mask.size),
                                jnp.sum(mask).astype(jnp.int32),
                                jnp.asarray(docs, jnp.int32)])
            if model_counts is not None:
                counts = jnp.concatenate([counts, model_counts])
            return loss / n, counts

        return loss_fn

    def _replica_share(self, batch, axis: str):
        """Inside a ``shard_map`` over the mesh axis ``axis``, ``batch``
        the member's own rows: what the member's loss (a mean over its own
        rows, as :meth:`_make_loss_fn` returns it) is multiplied by so that
        the ``psum`` over ``axis`` is the loss of the whole batch.  The
        logistic loss divides by its rows, which the members hold in equal
        shares (a power of two of them: the rescale is exact); the softmax
        loss by the positions ``target_mask`` marks, or 1 where there are
        none, which they do not."""
        if self.cfg.loss == "softmax_xent":
            own = jnp.sum(batch["target_mask"].astype(jnp.float32))
            return (jnp.maximum(own, 1.0)
                    / jnp.maximum(jax.lax.psum(own, axis), 1.0))
        return 1.0 / jax.lax.axis_size(axis)

    def _make_grad_fn(self):
        """``(params, batch) -> (loss, probs, grads)``; ``probs`` is the
        aux predicted probabilities when the quality sketch is armed,
        else None — one builder so every step variant gets the same
        arming rule.  (The softmax loss's counts are the sparse trainer's
        to carry; here they are dropped.)"""
        armed = self._quality_bins is not None
        loss_fn = self._make_loss_fn(with_probs=armed)
        if self.cfg.loss == "softmax_xent":
            def grad_fn(params, batch):
                (loss, _), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, batch)
                return loss, None, grads
        elif armed:
            def grad_fn(params, batch):
                (loss, probs), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, batch)
                return loss, probs, grads
        else:
            def grad_fn(params, batch):
                loss, grads = jax.value_and_grad(loss_fn)(params, batch)
                return loss, None, grads
        return grad_fn

    def _append_sketch(self, health, probs, batch, axis=None):
        """Concatenate the in-jit quality sketch onto a health vector;
        identity when unarmed (the unarmed payload stays byte-identical).
        ``axis`` sums per-shard sketches inside shard_map programs so the
        replicated output covers the full global batch."""
        qb = self._quality_bins
        if qb is None:
            return health
        sk = quality_mod.quality_sketch(probs, batch["labels"], qb)
        if axis is not None:
            sk = jax.lax.psum(sk, axis)
        return jnp.concatenate([health, sk])

    def _make_step(self):
        grad_fn = self._make_grad_fn()
        tx = self.tx

        def step(params, opt_state, batch):
            loss, probs, grads = grad_fn(params, batch)
            with annotate("step/update"):
                health = self._append_sketch(
                    _health_pack(loss, optax.global_norm(grads)), probs,
                    batch)
                updates, opt_state = tx.update(grads, opt_state, params)
                params = optim_lib.apply_updates(params, updates)
            return params, opt_state, loss, health

        return step

    def _make_zero_step(self):
        """Cross-replica sharded weight update (arXiv:2004.13336 / ZeRO-1):
        per-device grads -> ``psum_scatter`` (mean reduce-scatter over the
        data ring) -> each replica applies the optimizer to its 1/n shard of
        the flattened parameters with its 1/n shard of state ->
        ``all_gather`` of the new parameters.  One shard_map program; both
        collectives ride the ICI ring."""
        from jax.flatten_util import ravel_pytree
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        grad_fn = self._make_grad_fn()
        tx = self.tx
        mesh = self.mesh
        n = mesh.shape["data"]
        unravel = self._zero_unravel
        L, Lpad = self._zero_len, self._zero_pad
        shard_len = Lpad // n

        def local_step(params, opt_state, batch):
            loss, probs, grads = grad_fn(params, batch)
            flat_g, _ = ravel_pytree(grads)
            if Lpad != L:
                flat_g = jnp.pad(flat_g, (0, Lpad - L))
            g_shard = jax.lax.psum_scatter(
                flat_g, "data", scatter_dimension=0, tiled=True
            ) / n
            # ||mean grad|| from the disjoint scattered shards: one psum
            # of per-shard square sums — the health scalar, replicated
            gnorm = jnp.sqrt(jax.lax.psum(
                jnp.sum(g_shard * g_shard), "data"
            ))
            flat_p, _ = ravel_pytree(params)
            if Lpad != L:
                flat_p = jnp.pad(flat_p, (0, Lpad - L))
            idx = jax.lax.axis_index("data")
            p_shard = jax.lax.dynamic_slice(
                flat_p, (idx * shard_len,), (shard_len,)
            )
            updates, opt_state = tx.update(g_shard, opt_state, p_shard)
            # same dtype-preserving apply convention as the other step paths
            p_shard = optim_lib.apply_updates(p_shard, updates)
            full = jax.lax.all_gather(p_shard, "data", tiled=True)[:L]
            loss = jax.lax.pmean(loss, "data")
            health = self._append_sketch(
                _health_pack(loss, gnorm), probs, batch, axis="data")
            return unravel(full), opt_state, loss, health

        return shard_map(
            local_step,
            mesh=mesh,
            in_specs=(P(), P("data"), P("data")),
            out_specs=(P(), P("data"), P(), P()),
            check_vma=False,
        )

    def _make_compressed_step(self):
        """Data-parallel step whose gradient exchange is an explicit ring
        all-reduce with a quantile codec on every hop (wire-compressed
        training, the reference's production policy — paramserver.h:161-163,
        README.md:60).  Per-device grads are computed under shard_map, the
        flattened tree rides the compressed ring (dist/collectives.py), and
        every replica applies the identical decoded mean."""
        from jax.flatten_util import ravel_pytree
        from jax.sharding import PartitionSpec as P

        from lightctr_tpu.dist.collectives import _ring_all_reduce_local

        grad_fn = self._make_grad_fn()
        tx = self.tx
        mesh = self.mesh
        n = mesh.shape["data"]
        bits = self.compress_bits
        crange = self.compress_range
        cmode = self.compress_mode
        use_ef = self.error_feedback
        padded = self._ring_pad

        def local_step(params, state, batch):
            loss, probs, grads = grad_fn(params, batch)
            flat, unravel = ravel_pytree(grads)
            length = flat.shape[0]
            if padded != length:
                flat = jnp.pad(flat, (0, padded - length))
            if use_ef:
                flat, new_res = _ring_all_reduce_local(
                    flat, "data", n, average=True,
                    compress_bits=bits, compress_range=crange,
                    residual=state.residual[0], compress_mode=cmode,
                )
            else:
                flat = _ring_all_reduce_local(
                    flat, "data", n, average=True,
                    compress_bits=bits, compress_range=crange,
                    compress_mode=cmode,
                )
                new_res = state.residual[0]
            grads = unravel(flat[:length])
            # decoded mean gradient is replica-identical: so is its norm
            gnorm = optax.global_norm(grads)
            loss = jax.lax.pmean(loss, "data")
            updates, inner = tx.update(grads, state.inner, params)
            params = optim_lib.apply_updates(params, updates)
            state = CompressedRingState(inner=inner,
                                        residual=new_res[None])
            health = self._append_sketch(
                _health_pack(loss, gnorm), probs, batch, axis="data")
            return params, state, loss, health

        from jax import shard_map

        state_spec = CompressedRingState(inner=P(), residual=P("data"))
        return shard_map(
            local_step,
            mesh=mesh,
            in_specs=(P(), state_spec, P("data")),
            out_specs=(P(), state_spec, P(), P()),
            check_vma=False,
        )

    # ------------------------------------------------------------------

    def reset(self, params) -> None:
        """Reset trainer state to fresh (params, opt_state) while keeping all
        compiled step/scan caches — repeated benchmark runs from init without
        re-tracing."""
        self._params = self._own(params)
        self._opt_state = self._init_opt_state(self._params)

    def _own(self, params):
        """This trainer's own copy of a caller's tree, under its shardings:
        steps donate their input buffers, so the caller's tree must stay
        untouched (it may seed several trainers)."""
        params = tree_copy(params)
        shardings = self._param_sharding
        if shardings is not None:
            if isinstance(shardings, dict):
                # a subclass may copy some leaves itself
                shardings = {k: shardings[k] for k in params}
            params = jax.device_put(params, shardings)
        return params

    @property
    def params(self):
        return self._params

    @params.setter
    def params(self, tree) -> None:
        self._params = tree

    @property
    def opt_state(self):
        return self._opt_state

    @opt_state.setter
    def opt_state(self, tree) -> None:
        self._opt_state = tree

    def _init_opt_state(self, params):
        """Optimizer-state factory — subclasses with non-optax table state
        override this (so no transient full-size optax state is allocated)."""
        if self.zero_sharded:
            from jax.sharding import NamedSharding, PartitionSpec as P

            state = self.tx.init(jnp.zeros((self._zero_pad,), jnp.float32))
            for leaf in jax.tree_util.tree_leaves(state):
                if getattr(leaf, "shape", None) != (self._zero_pad,):
                    raise ValueError(
                        "zero_sharded needs an optimizer whose state is "
                        "elementwise over the parameters (adagrad/rmsprop/"
                        f"sgd-style); got a state leaf of shape "
                        f"{getattr(leaf, 'shape', None)}"
                    )
            # 1/n of the flattened state lives on each data replica
            return jax.device_put(
                state, NamedSharding(self.mesh, P("data"))
            )
        if self.compress_bits is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            n = self.mesh.shape["data"]
            # EF-off keeps a 1-element placeholder so the step signature
            # (and the scan carry) is one shape family either way
            residual = jnp.zeros(
                (n, self._ring_pad if self.error_feedback else 1),
                jnp.float32,
            )
            return CompressedRingState(
                inner=self.tx.init(params),
                residual=jax.device_put(
                    residual, NamedSharding(self.mesh, P("data"))
                ),
            )
        return self.tx.init(params)

    def _put(self, batch: Dict[str, np.ndarray]):
        if self.mesh is not None:
            # host arrays go straight to their shards: staging the whole
            # batch on one device first would make it hold all of it
            return shard_batch(self.mesh, {
                k: v if isinstance(v, jax.Array) else np.asarray(v)
                for k, v in batch.items()
            })
        return {k: jnp.asarray(v) for k, v in batch.items()}

    def train_step(self, batch: Dict[str, np.ndarray], *,
                   device_ready: bool = False) -> float:
        """One optimizer step.  ``device_ready=True`` asserts the batch
        already went through :meth:`_put` (a prefetch stage ran the
        pad+transfer off the critical path), so the step skips it — the
        ``input`` stepwatch phase then measures ~nothing, which is the
        point."""
        if not obs.enabled():
            dev_batch = batch if device_ready else self._put(batch)
            self._params, self._opt_state, loss, _ = self._step(
                self._params, self._opt_state, dev_batch
            )
            return loss
        # ONE instrumented body.  Each span is the shared null context
        # unless a root is taken (sampling rate, or a recording profiler
        # session): then the step is a tree in the span ring and on the
        # profiler's host timeline, beside the device's ops
        span = trace_mod.span
        t0 = time.perf_counter()
        sw = self.stepwatch
        with span("trainer/step", step=self._steps_seen + 1):
            with span("trainer/input") as sp:
                if sw is not None:
                    sw.mark("input")
                dev_batch = batch if device_ready else self._put(batch)
                if sp is not None:
                    # what crossed the boundary, counted only for a span
                    # that is recorded
                    sp.set(arrays=len(batch),
                           bytes=sum(v.nbytes for v in batch.values()),
                           devices=1 if self.mesh is None
                           else self.mesh.devices.size)
            with span("trainer/exec"):
                # dispatch of the jitted step, and any wait inside it (the
                # runtime blocks the host once its queue of dispatched
                # steps is full)
                if sw is not None:
                    sw.mark("exec")
                self._params, self._opt_state, loss, health = self._step(
                    self._params, self._opt_state, dev_batch
                )
            with span("trainer/record"):
                self._record_step(time.perf_counter() - t0, dev_batch,
                                  health=health)
        return loss

    # -- telemetry ------------------------------------------------------

    def _record_step(self, dt: float, batch, health=None) -> None:
        """Per-step metrics + one JSONL ``step`` event + the health feed.
        On async backends ``trainer_step_seconds`` measures dispatch (the
        caller's loss read forces the sync); on CPU it is the full step."""
        reg = self.telemetry
        self._steps_seen += 1
        n = int(batch["labels"].shape[0]) if "labels" in batch else 0
        reg.inc("trainer_steps_total")
        if n:
            reg.inc("trainer_examples_total", n)
        reg.observe("trainer_step_seconds", dt)
        obs.emit_event(
            "step", step=self._steps_seen, duration_s=round(dt, 6),
            examples=n, **self._step_event_fields(),
        )
        self._feed_health(batch, health)
        if self.resources is not None:
            self.resources.note_step()
        if self.device is not None:
            # specs-only registration (first call wins), EWMA time fold,
            # and the census counter — no analysis compile rides a step
            self.device.offer("trainer_step", self._step,
                              (self._params, self._opt_state, batch))
            self.device.note_step(dt, "trainer_step")
            self.device_census.maybe_sample()
        # armed profiler captures advance at step boundaries (one global
        # + one flag read when idle)
        device_mod.profile_step()
        if self.stepwatch is not None:
            self.stepwatch.step_completed(dt)

    #: blocking-fetch backpressure bound on the health scalar queue — a
    #: device more than this many steps behind gets synced rather than
    #: letting a NaN hide in an ever-growing backlog
    _HEALTH_MAX_LAG = 8

    def _feed_health(self, batch, health) -> None:
        """Per-step health vectors — ``[loss, grad_norm]``, then whatever
        else the step's program reports (:meth:`_vector_signals`) — and
        any signal a subclass makes on the host (:meth:`_health_signals`)
        into the health monitor.  ``wants`` gates the work: a monitor
        with no detector for any of them costs nothing here.  The
        vectors are queued as DEVICE values and drained oldest-first once
        materialized (``jax.Array.is_ready``) with ONE host fetch each,
        so the feed never syncs the in-flight step — a NaN step flips
        the verdict by the next recorded step (or on
        :meth:`flush_health`), at zero pipeline stalls."""
        hm = self.health
        on = hm is not None and health_mod.enabled()
        if on:
            sig = self._health_signals(batch)
            if sig:
                hm.observe(**sig)
        # the quality tracker drains the SAME queued vector (its sketch
        # tail), so an armed trainer feeds it even with health monitoring
        # off — the queue discipline below is identical either way
        want = (on and hm.wants("loss", "grad_norm",
                                *self._vector_signals())) \
            or self.quality is not None
        if health is None or not want:
            return
        pend = self._health_pending
        pend.append(health)
        while pend:
            head = pend[0]
            if (hasattr(head, "is_ready") and not head.is_ready()
                    and len(pend) <= self._HEALTH_MAX_LAG):
                break
            self._observe_scalars(hm if on else None, pend.pop(0))

    def _fetch_health(self, health) -> np.ndarray:
        """The single host fetch of a queued health vector: the one place
        the host waits for the device on purpose."""
        with trace_mod.span("trainer/health_fetch"):
            return np.asarray(health, np.float32)

    def _observe_scalars(self, hm, health) -> None:
        vals = self._fetch_health(health)
        if hm is not None:
            hm.observe(loss=float(vals[0]), grad_norm=float(vals[1]))
        self._feed_quality(vals, 2)

    def _feed_quality(self, vals: np.ndarray, head: int) -> None:
        """Everything past the ``head`` scalars of a drained health
        vector is the quality sketch (when armed): fold it into the
        tracker — same single fetch, no extra device traffic."""
        if self.quality is not None and vals.shape[0] > head:
            self.quality.update(vals[head:])

    def flush_health(self) -> None:
        """Drain every queued health vector NOW, blocking on any still in
        flight (end of a run, or a test that wants the verdict without
        running another step)."""
        hm = self.health
        pend, self._health_pending = self._health_pending, []
        on = hm is not None and health_mod.enabled()
        if not on and self.quality is None:
            return
        for entry in pend:
            self._observe_scalars(hm if on else None, entry)

    def arm_stepwatch(self, **kw) -> "stepwatch_mod.StepWatch":
        """Arm (or return) the step stall watchdog against this trainer's
        health monitor — the programmatic twin of ``LIGHTCTR_STALL=1``.
        Keyword arguments forward to
        :class:`~lightctr_tpu.obs.stepwatch.StepWatch`; passing any when
        a watch is already armed (e.g. from the env) REPLACES it, so a
        caller's explicit deadline/registry always wins."""
        if self.stepwatch is not None and kw:
            self.stepwatch.close()
            self.stepwatch = None
        if self.stepwatch is None:
            self.stepwatch = stepwatch_mod.StepWatch(
                monitor=self.health, **kw
            )
        return self.stepwatch

    def _health_signals(self, batch) -> Dict:
        """Extra health signals a subclass makes on the host per step (the
        sparse trainer's hybrid and hier steps count per-table touched
        uids here)."""
        return {}

    def _vector_signals(self) -> tuple:
        """Names of the signals a subclass's step carries in the health
        vector behind ``[loss, grad_norm]``: a monitor that wants one has
        the vector queued (the sparse trainer's one-program step carries
        ``table_touch``)."""
        return ()

    def _step_event_fields(self) -> Dict:
        """Extra fields subclasses contribute to each ``step`` event (the
        hybrid sparse trainer reports its exchange decisions here)."""
        return {}

    def _prefetch_prepare(self) -> Optional[Callable]:
        """The per-batch transform a prefetch stage runs OFF the step's
        critical path — pad+device-transfer for this trainer.  Subclasses
        whose step plans against the HOST batch (the sparse trainer's
        exchange planner) return None: prefetch then overlaps only the
        parse, and the step keeps its own ``_put``."""
        return self._put

    def _resolve_arrays(self, arrays):
        """``fit``/``fit_fullbatch_scan`` accept a compiled shard cache
        (:class:`~lightctr_tpu.data.ingest.ShardCache` or a cache
        directory) anywhere they accept an array dict — re-runs load
        pre-tokenized rows with zero parse work."""
        if isinstance(arrays, (str, ingest_mod.ShardCache)):
            return ingest_mod.as_arrays(arrays)
        return arrays

    def fit_stream(
        self,
        stream,
        max_steps: Optional[int] = None,
        prefetch: Optional[int] = None,
    ) -> list:
        """Drain a stream of padded batch dicts (the streaming reader,
        a shard-cache replay, …) through :meth:`train_step`.
        ``prefetch=K`` interposes :func:`~lightctr_tpu.data.ingest.
        prefetch_batches` with ``depth=K``: a worker thread keeps K
        parsed+padded+device-resident batches in flight behind the step
        (device transfer included whenever :meth:`_prefetch_prepare`
        provides one).  Returns the per-step losses."""
        prep = self._prefetch_prepare() if prefetch else None
        if prefetch:
            stream = ingest_mod.prefetch_batches(
                stream, depth=prefetch, prepare=prep,
                registry=self.telemetry)
        losses = []
        try:
            for batch in stream:
                losses.append(float(self.train_step(
                    batch, device_ready=prep is not None)))
                if max_steps is not None and len(losses) >= max_steps:
                    break
        finally:
            if hasattr(stream, "close"):
                stream.close()  # stop the prefetch worker promptly
        self.flush_health()
        if self.stepwatch is not None:
            self.stepwatch.pause()
        return losses

    def fit(
        self,
        arrays: Dict[str, np.ndarray],
        epochs: Optional[int] = None,
        batch_size: Optional[int] = None,
        eval_arrays: Optional[Dict[str, np.ndarray]] = None,
        eval_every: int = 0,
        verbose: bool = False,
        prefetch: Optional[int] = None,
    ) -> Dict[str, list]:
        arrays = self._resolve_arrays(arrays)
        epochs = epochs if epochs is not None else self.cfg.epochs
        n_rows = len(next(iter(arrays.values())))
        if batch_size is not None and batch_size > n_rows:
            raise ValueError(
                f"batch_size={batch_size} exceeds dataset size {n_rows} "
                "(drop_remainder would yield zero batches); use batch_size=None "
                "for full-batch training"
            )
        history = {"loss": [], "eval": []}
        t0 = time.perf_counter()
        full_batch = self._put(arrays) if batch_size is None else None
        for epoch in range(epochs):
            if batch_size is None:
                self._params, self._opt_state, loss, _ = self._step(
                    self._params, self._opt_state, full_batch
                )
            else:
                loss = None
                inner = minibatches(arrays, batch_size,
                                    seed=self.cfg.seed + epoch)
                if prefetch:
                    prep = self._prefetch_prepare()
                    inner = ingest_mod.prefetch_batches(
                        inner, depth=prefetch, prepare=prep,
                        registry=self.telemetry)
                    for batch in inner:
                        loss = self.train_step(
                            batch, device_ready=prep is not None)
                else:
                    for batch in inner:
                        loss = self.train_step(batch)
            history["loss"].append(float(loss))
            ev = None
            if eval_every and eval_arrays is not None and (epoch + 1) % eval_every == 0:
                ev = self.evaluate(eval_arrays)
                history["eval"].append((epoch, ev))
            obs.emit_event("epoch", epoch=epoch, loss=float(loss),
                           **({"eval": ev} if ev is not None else {}))
            if verbose:
                ensure_console_logging()
                _LOG.info("epoch %d: loss=%.5f%s", epoch, float(loss),
                          f" {ev}" if ev is not None else "")
        self.flush_health()  # the last step's pending scalars
        if self.stepwatch is not None:
            # training is DONE — the deadman must not read post-fit idle
            # time as a wedge; the next train_step re-arms it
            self.stepwatch.pause()
        history["wall_time_s"] = time.perf_counter() - t0
        return history

    def fit_fullbatch_scan(self, arrays: Dict[str, np.ndarray], epochs: int) -> np.ndarray:
        """Run ``epochs`` full-batch steps as one on-device ``lax.scan`` —
        zero per-epoch dispatch, the TPU equivalent of the reference's
        T-epoch re-train loops (main.cpp:227-229).  Returns the loss
        trajectory."""
        batch = self._put(self._resolve_arrays(arrays))
        run = self._get_scan_fn(epochs)
        self._params, self._opt_state, losses = run(
            self._params, self._opt_state, batch)
        return np.asarray(losses)

    def warmup_fullbatch_scan(self, arrays: Dict[str, np.ndarray], epochs: int) -> None:
        """Warm the scan's jit cache without touching trainer state —
        benchmark warm-up.  NOTE: this EXECUTES one full throwaway scan
        (``epochs`` steps) on COPIES of (params, opt_state): a compile-only
        ``lower().compile()`` does not warm ``jax.jit``'s call cache, so a
        timed first call would still pay a retrace+link; and the scan
        donates its argument buffers, hence the copies."""
        batch = self._put(arrays)
        run = self._get_scan_fn(epochs)
        out = run(tree_copy(self._params), tree_copy(self._opt_state), batch)
        jax.block_until_ready(out)

    def _get_scan_fn(self, epochs: int):
        run = self._scan_cache.get(epochs)
        if run is None:
            step = self._build_step()

            def body_fn(batch):
                def body(carry, _):
                    params, opt_state = carry
                    # the grad-norm health scalar is unused here, so XLA
                    # DCEs it out of the scanned program — scan stays free
                    params, opt_state, loss, _ = step(
                        params, opt_state, batch
                    )
                    return (params, opt_state), loss

                return body

            @partial(jax.jit, donate_argnums=(0, 1))
            def run(params, opt_state, batch):
                (params, opt_state), losses = jax.lax.scan(
                    body_fn(batch), (params, opt_state), None, length=epochs
                )
                return params, opt_state, losses

            self._scan_cache[epochs] = run
        return run

    def predict_proba(self, arrays: Dict[str, np.ndarray]) -> np.ndarray:
        return np.asarray(sigmoid(self._logits_j(self.params, self._put(arrays))))

    def evaluate(
        self, arrays: Dict[str, np.ndarray], batch_size: Optional[int] = None
    ) -> Dict[str, float]:
        """Logloss / accuracy / AUC report, matching FM_Predict
        (fm_predict.cpp:56-77).  With ``batch_size``, evaluation streams in
        fixed-size chunks with running sums + streaming AUC histograms —
        memory-bounded for epoch-scale sets (the histogram AUC's purpose)."""
        with annotate("trainer/eval",
                      examples=int(len(arrays["labels"]))):
            return self._evaluate(arrays, batch_size)

    def _evaluate(
        self, arrays: Dict[str, np.ndarray], batch_size: Optional[int] = None
    ) -> Dict[str, float]:
        labels_all = arrays["labels"]
        n = len(labels_all)
        if batch_size is None or batch_size >= n:
            probs = self.predict_proba(arrays)
            probs_j = jnp.asarray(probs)
            labels_j = jnp.asarray(labels_all)
            return {
                "logloss": float(metrics_lib.logloss(probs_j, labels_j)),
                "accuracy": float(
                    metrics_lib.accuracy(
                        (probs_j > 0.5).astype(jnp.int32), labels_j.astype(jnp.int32)
                    )
                ),
                "auc": float(
                    metrics_lib.auc_histogram(probs_j, labels_j.astype(jnp.int32))
                ),
            }
        auc = metrics_lib.StreamingAUC()
        params = self.params  # once a call: a subclass's view is made anew
        loss_sum = 0.0
        correct = 0.0
        seen = 0
        for s in range(0, n, batch_size):  # includes the tail remainder
            chunk = {k: v[s : s + batch_size] for k, v in arrays.items()}
            m = len(chunk["labels"])
            # stay on device: logits -> sigmoid -> metrics without a host trip
            probs_j = sigmoid(self._logits_j(params, self._put(chunk)))
            labels_j = jnp.asarray(chunk["labels"])
            loss_sum += float(metrics_lib.logloss(probs_j, labels_j)) * m
            correct += float(
                jnp.sum((probs_j > 0.5).astype(jnp.int32) == labels_j.astype(jnp.int32))
            )
            auc.update(probs_j, labels_j.astype(jnp.int32))
            seen += m
        return {
            "logloss": loss_sum / seen,
            "accuracy": correct / seen,
            "auc": auc.result(),
        }
