"""O(touched-rows) training for huge-vocab CTR models.

The reference PS updates only the keys a batch pushed
(``paramserver.h:287-295`` walks the pushed map); a plain JAX
``value_and_grad`` over a [vocab, dim] table materializes a DENSE gradient
and the optax update walks every row — O(vocab) per step, ruinous at
Criteo vocabularies (2^20+ rows for a few thousand touched).

:class:`SparseTableCTRTrainer` restores O(touched) without changing the
model code, exploiting that our models only use their tables via
``jnp.take(params[k], batch[field], axis=0)``:

  1. per step, dedup each table's batch ids: ``uids, inv = unique(ids)``
     (static shape: ``size=ids.size`` padded with id 0);
  2. gather ``rows = table[uids]`` — O(touched);
  3. rewrite the batch's id fields to POSITIONS (``inv``) and substitute
     the rows for the table leaf, so the unchanged model computes on the
     gathered rows;
  4. differentiate w.r.t. the rows ([n_unique, dim], O(touched)) and the
     dense leaves;
  5. dense leaves update through optax; table rows through the sparse
     Adagrad recipe of :func:`lightctr_tpu.embed.table.sparse_adagrad_update`
     (accum rows += g^2; w rows -= lr*g*rsqrt(accum+eps)) scattered back at
     ``uids``.

The trajectory is EXACTLY the dense Adagrad trainer's: untouched rows have
zero gradient there, so neither their weights nor their accumulators move
(parity-tested).  Padded dedup slots repeat id 0 and are never referenced
by ``inv``, so they carry zero gradient and their scatter contribution is
a no-op ``add``.

Scope: Adagrad (the reference PS's workhorse); single-device, data-sharded
batches, and PS-style ``param_shardings`` (tables row-sharded over the
``embed`` axis: each shard gathers and applies the rows it owns, on the
rung of the ladder its own rows need, inside a ``shard_map`` over that
axis, and one ``psum`` joins the gathered rows — the reference's
worker→PS-shard pull/push topology, pull.h:50-99 /
distributed_algo_abst.h:176-280; where the mesh's ``data`` axis holds more
than one replica the loss and its gradient run a replica inside a
``shard_map`` and one flat ``psum`` at the live prefix's rung joins the
replicas' row gradients, ``sparse_kernels.join_live``; the rest of the
step stays one GSPMD program).

Multi-device replicated data parallelism (``mesh`` given, no
``param_shardings``) runs an EXPLICIT hybrid exchange instead of letting
XLA psum the dense [vocab, dim] table gradients — Parallax's split by
variable type (arXiv:1808.02621) fused with SparCML's sparse allreduce
(arXiv:1802.08021), per step, one shard_map program:

  - each replica dedups its LOCAL batch shard's ids and differentiates
    w.r.t. its gathered rows (O(touched) as above); tables listing the
    IDENTICAL field tuple share one id stream — unique runs once per
    stream and the exchange ships the ids once per (stream, algorithm)
    group;
  - table-leaf gradients ride the cheaper of TWO sparse collectives:
    ``sparse_all_reduce`` (one all_gather of (uids, g_rows) pairs —
    O(touched) ids+values instead of the dense ring's O(vocab)) or the
    owner-partitioned ``sparse_reduce_scatter`` (contributions routed to
    the id's ``uid % n`` owner over a ppermute ring, merged there, only
    merged owner shards all-gathered — O(touched) TOTAL, roughly flat in
    world size where the allgather grows linearly); either way every
    replica applies the IDENTICAL ``sparse_adagrad_update`` on the merged
    union, so replicas cannot diverge;
  - per table, a static trace-time three-way pick
    (``pick_exchange_algo``: dense ring | sparse allgather | sparse
    reduce-scatter, from density, vocab, dim and world size) falls back
    to the dense (optionally quantized) ring when neither sparse payload
    beats the [vocab, dim] buffer — SparCML's dense switch-over, so the
    worst case never regresses.  The taken decision is recorded in
    ``self.exchange_policy`` ({table: "sparse" | "sparse_rs" | "dense"});
  - reduce-scatter capacities are expected sizes with slack, so every
    batch is checked host-side (``rs_fits``) before dispatch; a batch
    that would overflow runs an allgather fallback program instead
    (counted in ``trainer_rs_fallback_total``) — exactness never rides
    on the capacity guess;
  - dense leaves keep the existing exchange: the quantile-compressed
    explicit ring when ``compress_bits`` is set (EF-SGD residual and all,
    exactly CTRTrainer's compressed path), a plain psum mean otherwise.
    With ``compress_bits`` the sparse value payload is quantile-coded
    too — but single-shot (one encode per value per step, decoded before
    the merge), so it needs no error feedback: unlike the ring there is
    no per-hop noise accumulation.

The exchanged trajectory matches the dense-psum data-parallel trainer to
fp32 tolerance (parity-tested): merged mean row gradients equal the dense
mean gradient's touched rows, and untouched rows move in neither world.

MULTI-HOST replicated data parallelism (``hier_exchange`` given — a
:class:`~lightctr_tpu.dist.hier.HierExchangeClient`) runs the
HIERARCHICAL two-level exchange instead (docs/SPARSE_EXCHANGE.md): the
local mesh's replicas merge touched rows in-jit first (program A), the
host ships exactly ONE merged (uids, rows) payload per table over the
DCN reduce rendezvous and pulls the cross-host merge back (the wire
hop), and a second jitted program applies the identical global mean on
every replica (program C) — cross-host bytes stay O(touched-per-host)
regardless of local replica count, and the trajectory still equals the
dense-psum trainer over the GLOBAL batch (2-process acceptance-tested).

Platform note: the step donates (params, opt_state), so on accelerators
the row scatters update the tables in place and the step is truly
O(touched).  XLA's CPU backend does not honor donation — there each step
still pays an O(vocab) table copy (measured: the step beats the dense
trainer by the eliminated gradient+optimizer passes only).

Layout note: the one-program step keeps a ``[V, d]`` table whose ``d``
divides 128 at least four times in ONE fused store with its accumulator,
``[2V // r, 128]`` under the table's key of ``_params`` (``r = 128 // d``:
``ops.sparse_kernels.lane_pack`` / ``lane_fused``; docs/KERNELS.md "The
fused store"): a lane row holds ``r // 2`` ids, each id's row lanes then
its accumulator lanes, so the forward gather and the apply's read whole
128-lane rows and the apply is one scatter of them.  ``params`` / ``opt_state`` show
logical tables and accumulators, by a view made on each read;
``train_step`` works on the stored form.

Kernel note: the per-step sparse tax — id dedup, row gather, segment
merge, row apply, payload pack — is one function each in
:mod:`lightctr_tpu.ops.sparse_kernels`, the same implementation on every
backend; only the payload packers have a Pallas form, taken on a TPU (see
docs/KERNELS.md).
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import shard_map
from jax.flatten_util import ravel_pytree
from jax.sharding import PartitionSpec as P

from lightctr_tpu import obs
from lightctr_tpu.models.ctr_trainer import (CTRTrainer, _health_pack,
                                              softmax_count_names)
from lightctr_tpu.obs import device as obs_device
from lightctr_tpu.obs import health as health_mod
from lightctr_tpu.obs import quality as quality_mod
from lightctr_tpu.obs import trace as trace_mod
from lightctr_tpu.ops.sparse_kernels import next_pow2 as _pow2_pad
from lightctr_tpu.utils.profiling import annotate

def _hier_local_algo(n: int, kpad: int, vocab: int, dims,
                     force_ag: bool = False):
    """The ONE local ag-vs-rs comparison for the hierarchical exchange's
    ICI merge stage -> ``(algo, rs_caps, per_table_bytes)``.  Both the
    traced local-merge program and its host-side plan mirror call this,
    so the capacities the compiled program uses and the ones
    ``_rs_batch_fits`` checks cannot drift.  The dense ring is not a
    candidate: the wire needs a sparse union.  Ids are priced once per
    stream (``include_ids`` on the first table only)."""
    from lightctr_tpu.dist.collectives import (
        rs_default_caps, sparse_exchange_bytes, sparse_rs_bytes,
    )

    ag = [sparse_exchange_bytes(n, kpad, d, include_ids=(i == 0))
          for i, d in enumerate(dims)]
    caps = rs_default_caps(n, kpad, vocab)
    rs = [sparse_rs_bytes(n, caps[0], caps[1], d, include_ids=(i == 0))
          for i, d in enumerate(dims)]
    if force_ag or sum(ag) <= sum(rs):
        return "sparse", None, ag
    return "sparse_rs", caps, rs


#: every ``trainer_*`` telemetry series this module emits — the AST lint in
#: tests/test_obs.py pins emissions to this declaration (and declarations to
#: emissions), so an exchange counter can never ship dark or go stale
EXCHANGE_SERIES = (
    "trainer_exchange_bytes_total",      # {table, policy} bytes/step
    "trainer_exchange_algo_total",       # {table, algo} steps per decision
    "trainer_sparse_exchange_bytes_total",
    "trainer_sparse_rs_bytes_total",
    "trainer_dense_ring_bytes_total",
    "trainer_hier_wire_bytes_total",     # hierarchical: DCN hop, per host
    "trainer_hier_local_bytes_total",    # hierarchical: ICI merge hop
    "trainer_hier_wire_packed_bytes_total",   # measured socket bytes/step
    "trainer_hier_wire_fp32_bytes_total",     # fp32 equiv of same payload
    "trainer_hier_wire_id_saved_bytes_total",  # shared-stream id savings
    "trainer_hier_wire_ef_mass",         # gauge: member EF residual mass
    # streaming rendezvous (ISSUE 16): chunked dispatch + compute/push
    # overlap — chunk fill is rows/capacity, overlap ratio is
    # 1 - blocked/push (metrics_report --exchange derives both)
    "trainer_hier_chunk_pushes_total",    # chunk frames dispatched
    "trainer_hier_chunk_rows_total",      # rows those chunks carried
    "trainer_hier_chunk_capacity_rows_total",  # rows the windows could hold
    "trainer_hier_overlap_push_seconds_total",   # dispatch->commit wall
    "trainer_hier_overlap_blocked_seconds_total",  # of which commit blocked
    "trainer_rs_fallback_total",
    "trainer_rs_overflow_total",
    # tiered device fast path (TieredDeviceEmbedding, ISSUE 15)
    "trainer_tiered_fast_steps_total",   # all-hot steps (no store surface)
    "trainer_tiered_fast_rows_total",    # rows through the aliased apply
    "trainer_tiered_pushed_rows_total",  # non-resident rows via push_batch
    "trainer_tiered_stale_tickets_total",  # adopt refused: residency moved
    # the sized XLA apply (ops.sparse_kernels.apply_ladder): live rows a
    # step against the slots of the rung it took; their ratio is the live
    # share (metrics_report --kernels)
    "trainer_apply_live_rows_total",     # {table[, shard]}
    "trainer_apply_slots_total",         # {table[, shard]}
    # lane rows the apply writes, fused stores only: over the live rows,
    # how many of them share a lane row (1 is none, 2/r is all)
    "trainer_apply_lane_rows_total",     # {table[, shard]}
    # slots the apply's scatters walk: the rung's slots once where table
    # and accumulator are one fused store, twice where they are two arrays
    "trainer_apply_scatter_slots_total",  # {table[, shard]}
    # steps whose table_touch (and the apply's counters above) came off the
    # step's own health vector ("device": the one-program step) against
    # steps whose ids the host counted ("host": the hybrid and hier steps)
    "trainer_health_signals_total",      # {source}
)


def _pack_counts(counts):
    """int32 ``[n]`` -> f32 ``[2n]``: the low 16 bits of each, then the
    high ones.  A half is exact in an f32 whatever the count (159,744
    would fit one whole, a stream of 2^24 ids would not) and stays exact
    through the sum with zeros by which a mesh may replicate the vector;
    a bitcast would ride as a denormal, which a TPU flushes."""
    c = counts.astype(jnp.int32)
    return jnp.concatenate([c & 0xFFFF, c >> 16]).astype(jnp.float32)


def _unpack_counts(vals: np.ndarray) -> list:
    """The host's inverse of :func:`_pack_counts`: Python ints."""
    halves = vals.astype(np.int64)
    n = halves.shape[0] // 2
    return (halves[:n] + (halves[n:] << 16)).tolist()


class _StepCounts:
    """The integers the one-program step's health vector carries behind
    ``[loss, grad_norm]`` (ahead of the quality sketch): :meth:`pack`
    makes them in the jitted step, :meth:`read` is what the host makes of
    them once the vector is drained.  In order: per id stream the dedup's
    distinct ``count`` and the stream's length; per table — per row shard
    where ``shards`` (``{table: shards}``) names the table — the live
    slots of the apply's plan, the ``branch`` its switches took and, for
    a table in a fused store, the lane rows it wrote
    (``sparse_kernels.apply_counts``); last, where the loss is the
    softmax cross-entropy, the integers its loss function counts
    (``model``: ``ctr_trainer.softmax_count_names``, each a counter the
    host adds the step's value to).  All static but the values: built
    with the step, from what the step is built from."""

    def __init__(self, spec, vocab: Dict[str, int], shards: Dict[str, int],
                 lane_pack: Dict[str, int], model: tuple = (),
                 joins: Optional[Dict[str, tuple]] = None):
        self._groups = SparseTableCTRTrainer._field_groups(spec)
        self._vocab = vocab
        # {table: (bytes a row, the policies of the joins the mesh step
        # makes of the table's [K, ...] rows)} (``sparse_kernels.join_live``)
        self._joins = joins or {}
        self.model = tuple(obs.labeled(name, **labels)
                           for name, labels in model)
        # (table, its lane_pack r — over 1: a fused store —, the labels of
        # its counters a shard)
        self._apply = [
            (k, lane_pack.get(k, 1),
             [{"table": k, "shard": i} for i in range(shards[k])]
             if k in shards else [{"table": k}])
            for k in spec]
        #: f32 slots of the vector the counts take: two an integer
        self.width = 2 * (2 * len(self._groups) + sum(
            (2 + (r > 1)) * len(per) for _, r, per in self._apply)
            + len(self.model))

    def pack(self, distinct, uids, batch, model_counts=None):
        """The ``width`` slots, inside the step: ``distinct`` is
        ``_dedup_and_gather``'s ``{field_tuple: count}``, ``uids`` its
        ``{table: uids}``.  Each shard's plan is made from the replicated
        ``uids`` as the shard itself makes it, on every chip alike, so no
        collective has to bring it out of the apply's ``shard_map``."""
        from lightctr_tpu.ops import sparse_kernels

        counts = [jnp.stack([distinct[fields],
                             sum(batch[f].size for f in fields)])
                  for fields in self._groups]
        for k, r, per in self._apply:
            rows = self._vocab[k] // len(per)
            counts += [sparse_kernels.apply_counts(
                uids[k], rows, i * rows if len(per) > 1 else None, r)
                for i in range(len(per))]
        if self.model:
            counts.append(model_counts)
        return _pack_counts(jnp.concatenate(counts))

    def read(self, vals: np.ndarray, reg, tables: bool = True) -> Dict:
        """``vals``: the vector's ``width`` count slots.  Adds the
        model's counts to their counters on ``reg`` and, with ``tables``,
        increments the apply's — live rows, the slots of the rung the
        device's switch took (the ladder's, or all K on the undeclared
        branch), those slots once a scatter the apply makes there (one
        into a fused store, else table and accumulator), lane rows — and
        the bytes a member hands each join of the mesh step's
        (``trainer_exchange_bytes_total``: the slots of the rung the whole
        live prefix takes, from the stream's distinct count, times the
        bytes of a row), and returns the skew detector's
        ``table_touch``."""
        from lightctr_tpu.ops import sparse_kernels

        ints = _unpack_counts(vals)
        if self.model:
            for name, value in zip(self.model, ints[-len(self.model):]):
                reg.inc(name, value)
        if not tables:
            return {}
        ints = iter(ints)
        touch = {}
        for tables in self._groups.values():
            unique, ids = next(ints), next(ints)
            for k in tables:
                touch[k] = {"unique": unique, "ids": ids,
                            "vocab": self._vocab[k]}
        for k, r, per in self._apply:
            ladder = sparse_kernels.apply_ladder(touch[k]["ids"])
            for labels in per:
                reg.inc(obs.labeled("trainer_apply_live_rows_total",
                                    **labels), next(ints))
                slots = (ladder + ladder[-1:])[next(ints)]
                reg.inc(obs.labeled("trainer_apply_slots_total", **labels),
                        slots)
                reg.inc(obs.labeled("trainer_apply_scatter_slots_total",
                                    **labels), slots * (1 if r > 1 else 2))
                if r > 1:
                    reg.inc(obs.labeled("trainer_apply_lane_rows_total",
                                        **labels), next(ints))
        for k, (row_bytes, policies) in self._joins.items():
            slots = sparse_kernels.ladder_slots(touch[k]["ids"],
                                                touch[k]["unique"])
            for policy in policies:
                reg.inc(obs.labeled("trainer_exchange_bytes_total",
                                    table=k, policy=policy),
                        slots * row_bytes)
        return touch


class SparseTableCTRTrainer(CTRTrainer):
    """CTRTrainer whose listed table leaves update O(touched) per step.

    Parameters (beyond CTRTrainer's)
    --------------------------------
    sparse_tables: {param_key: [batch_id_field, ...]} — top-level param
        leaves that are [rows, ...] tables indexed ONLY via ``jnp.take``
        with the listed batch fields (e.g. Wide&Deep:
        ``{"w": ["fids"], "embed": ["rep_fids"]}``).
    compress_bits / compress_range / compress_mode / error_feedback:
        as in CTRTrainer, applied to the HYBRID multi-device exchange
        (mesh given, replicated params): dense leaves ride the compressed
        explicit ring, table leaves' sparse value payloads are coded with
        the same table (single-shot, no EF needed — see module docstring).
    dense_switch_margin: scale on the SparCML density switch — a table
        leaf takes the sparse exchange only while its padded sparse bytes
        stay under ``margin * dense_ring_bytes``; below 1.0 demands a real
        win before leaving the worst-case-safe dense path.
    hier_exchange: a :class:`~lightctr_tpu.dist.hier.HierExchangeClient`
        — arms the HIERARCHICAL two-level exchange (docs/SPARSE_EXCHANGE.md):
        the local mesh's replicas merge touched rows in-jit first (the
        cheaper of the two sparse collectives, owner-partition family),
        then exactly ONE merged (uids, rows) payload per host rides the
        DCN reduce rendezvous, and the pulled cross-host merge broadcasts
        back over the ICI into a second jitted apply program — cross-host
        bytes stay O(touched-per-host) regardless of local replica count.
        Requires a mesh (the local replicas), replicated params, and the
        exact exchange (``compress_bits=None`` — the wire codec is the
        client's knob); every branch, local-overflow fallback included,
        stays dense-psum-exact.
    """

    def __init__(
        self,
        params,
        logits_fn,
        cfg,
        sparse_tables: Dict[str, Sequence[str]],
        l2_fn=None,
        fused_fn=None,
        mesh=None,
        param_shardings=None,
        eps: float = 1e-7,
        compress_bits: Optional[int] = None,
        compress_range: float | str = 1.0,
        compress_mode: Optional[str] = None,
        error_feedback: Optional[bool] = None,
        dense_switch_margin: float = 1.0,
        hier_exchange=None,
        quality_bins: Optional[int] = None,
    ):
        if not sparse_tables:
            raise ValueError("sparse_tables must name at least one table leaf")
        for k in sparse_tables:
            if k not in params:
                raise ValueError(f"sparse_tables key {k!r} not in params")
        self._spec = {k: tuple(v) for k, v in sparse_tables.items()}
        # A batch field shared by two tables is only coherent when both
        # tables list the IDENTICAL field tuple (then their unique/inverse
        # mappings coincide and the position rewrite is the same).  Any
        # other overlap would silently rewrite the field with the LAST
        # table's inverse and train the wrong rows of the others.
        owner: Dict[str, str] = {}
        for k, fields in self._spec.items():
            for f in fields:
                if f in owner and self._spec[owner[f]] != self._spec[k]:
                    raise ValueError(
                        f"batch field {f!r} is listed under tables "
                        f"{owner[f]!r} {self._spec[owner[f]]} and {k!r} "
                        f"{self._spec[k]} with different field tuples — "
                        "the position rewrite would be ambiguous"
                    )
                owner[f] = k
        self._eps = eps
        self._dense_margin = dense_switch_margin
        # the tables as the caller and every reader of ``params`` see them;
        # {table: r} names the ones this trainer keeps, accumulator and
        # all, in a fused store (:meth:`_plan_lane_pack` settles it, from
        # shapes alone)
        self._table_shapes = {k: tuple(np.shape(params[k]))
                              for k in self._spec}
        self._lane_pack: Dict[str, int] = {}
        self._fuse_j = self._unfuse_j = None
        # mesh WITHOUT explicit shardings = replicated data parallelism:
        # the explicit hybrid exchange replaces XLA's dense psum.  With
        # param_shardings (embed-axis row sharding) GSPMD owns the
        # collectives and the single-program step below is kept.
        self._hybrid_dp = mesh is not None and param_shardings is None
        # hierarchical mode: the hybrid one-program step is replaced by a
        # local-merge program + the DCN wire hop + an apply program
        self._hier = hier_exchange is not None
        if self._hier:
            if mesh is None or param_shardings is not None:
                raise ValueError(
                    "hier_exchange needs a mesh of replicated local "
                    "replicas (no param_shardings)"
                )
            if compress_bits is not None:
                raise ValueError(
                    "hier_exchange owns its wire codec via the "
                    "HierExchangeClient knob (codec='f16'/'q8_ef'/"
                    "'q4_ef'); compress_bits must stay None"
                )
            self._hybrid_dp = False
        if cfg.loss != "logistic" and (self._hybrid_dp or self._hier):
            raise ValueError(
                f"loss={cfg.loss!r} runs on the one-program step (one device, "
                "or a mesh with param_shardings): the hybrid and the "
                "hierarchical exchange build the logistic loss")
        # the hybrid and the hierarchical steps keep logical tables
        if not (self._hybrid_dp or self._hier):
            self._plan_lane_pack(param_shardings)
        # {table: "sparse" | "sparse_rs" | "dense"} — the three-way
        # trace-time pick each table leaf got (diagnostics / tests):
        # allgather sparse exchange, owner-partitioned reduce-scatter, or
        # the dense ring past the density switch
        self.exchange_policy: Dict[str, str] = {}
        # {table: bytes each member transmits per step under the decision
        # above} — written at trace time with the SAME accounting helpers
        # the benches use (dist.collectives.sparse_exchange_bytes /
        # sparse_rs_bytes / dense_ring_bytes), so live counters and BENCH
        # JSONs cannot disagree
        self.exchange_bytes_per_step: Dict[str, int] = {}
        self._exchange_logged = False
        # what the one-program step's health vector carries behind [loss,
        # grad_norm] (_make_step builds it with the step); None where the
        # program that runs carries no counts and the host counts the ids
        self._step_counts: Optional[_StepCounts] = None
        # reduce-scatter capacity safety net: rs capacities are EXPECTED
        # sizes with slack (dist.collectives.rs_default_caps), so every
        # batch is checked HOST-side (rs_fits) before dispatch and one
        # that would overflow runs the allgather fallback program instead
        # — exactness never rides on the capacity guess.  The (rare)
        # fallback trace records into its own dicts so it cannot shadow
        # the primary program's decisions.
        self._force_ag = False
        self._step_ag = None
        self._fallback_policy: Dict[str, str] = {}
        self._fallback_bytes: Dict[str, int] = {}
        self._last_step_fallback = False
        self._fallback_logged = False
        self._plan_cache: Dict = {}
        self._scan_cache_ag: Dict = {}
        # hierarchical-exchange state: the wire client, the per-step round
        # counter (every host's trainer steps in lockstep, so the counter
        # IS the round id), the fixed table-id order the rendezvous keys
        # rounds by (ctor args are identical on every host), and the
        # trace-time local-merge decisions (primary / ag-fallback program
        # families record separately, as the hybrid fallback does)
        self._hier_client = hier_exchange
        self._hier_epoch = 0
        self._hier_tables = list(self._spec)
        self.hier_local_policy: Dict[str, str] = {}
        self.hier_local_bytes_per_step: Dict[str, int] = {}
        self._hier_fb_local_policy: Dict[str, str] = {}
        self._hier_fb_local_bytes: Dict[str, int] = {}
        self._hier_last_local = False  # last step ran the ag fallback
        self._hier_wire_dense_bytes = 0
        # per-step wire-codec honesty numbers (ISSUE 13): measured socket
        # bytes, the fp32-equivalent of the same payload, shared-id savings
        self._hier_wire_packed_bytes = 0
        self._hier_wire_fp32_bytes = 0
        self._hier_wire_id_saved = 0
        # streaming-rendezvous overlap numbers (ISSUE 16): per-step chunk
        # dispatch counts (deltas of the client's counters) and the
        # dispatch->commit wall split into total vs commit-blocked seconds
        self._hier_chunk_pushes = 0
        self._hier_chunk_rows = 0
        self._hier_chunk_capacity = 0
        self._hier_push_seconds = 0.0
        self._hier_blocked_seconds = 0.0
        self._hier_local_j = None
        self._hier_local_ag_j = None
        self._hier_apply_j = None
        super().__init__(
            params, logits_fn, cfg, l2_fn=l2_fn, fused_fn=fused_fn, mesh=mesh,
            param_shardings=param_shardings, compress_bits=compress_bits,
            compress_range=compress_range, compress_mode=compress_mode,
            error_feedback=error_feedback, quality_bins=quality_bins,
        )
        if self._hier:
            import jax as _jax

            self._hier_local_j = _jax.jit(self._make_hier_local_step())
            self._hier_apply_j = _jax.jit(
                self._make_hier_apply_step(), donate_argnums=(0, 1)
            )
            # the base ctor jitted _build_step()'s program; the hier step
            # is a HOST orchestrator around two jitted programs instead
            self._step = self._hier_step
            if self.resources is not None:
                # the pow2-padded hier program family: cache-entry growth
                # here is the ladder warming (expected) or a shape leak
                # (the recompile-storm detector's case)
                self.resources.track("hier_local_step", self._hier_local_j)
                self.resources.track("hier_apply_step", self._hier_apply_j)
        # table trainers also watch per-table touched-uid skew (the same
        # id streams the sparse exchange dedups — hot/dead detection)
        if self.health is not None:
            health_mod.ensure_trainer_detectors(self.health, tables=True)

    # -- state -------------------------------------------------------------

    def _ring_tree(self, params):
        """Only the dense leaves ride the compressed ring — the table
        leaves have their own sparse exchange (Parallax's split)."""
        return {k: v for k, v in params.items() if k not in self._spec}

    def _use_sparse_ef(self) -> bool:
        """Fixed-range clipped sparse payloads get the per-table EF carry
        on BOTH sparse exchange paths (allgather since PR 7, reduce-
        scatter since PR 9): hybrid exchange + compress_bits + error
        feedback + a FIXED float compress_range (dynamic never clips, so
        a carry would compensate nothing)."""
        return (
            self._hybrid_dp
            and self.compress_bits is not None
            and self.error_feedback
            and isinstance(self.compress_range, (int, float))
        )

    def _plan_lane_pack(self, param_shardings) -> None:
        """Which tables the one-program step keeps in a fused store,
        ``{table: r}`` (``sparse_kernels.lane_pack``: ``d`` divides 128,
        ``r = 128 // d`` >= 4, every row shard a multiple of ``r`` rows —
        shapes and the caller's shardings, nothing else), and the two
        jitted relayouts between a logical ``[V, d]`` half and the ``[2V
        // r, 128]`` store under the table's own ``PartitionSpec``: over
        the fused leaves together, each shard relaying its own rows."""
        from lightctr_tpu.ops import sparse_kernels

        shardings = param_shardings
        if not isinstance(shardings, dict):
            shardings = dict.fromkeys(self._spec, shardings)
        pack, placed = self._lane_pack, {}
        for k, shape in self._table_shapes.items():
            sh = shardings.get(k)
            if sh is not None and not hasattr(sh, "spec"):
                continue
            r = sparse_kernels.lane_pack(
                shape, sh.shard_shape(shape) if sh else None)
            if r > 1:
                pack[k] = r
                if sh is not None:
                    placed[k] = sh

        def relayout(one, donate=()):
            def leaf(k, half, *arrays):
                fn = partial(one, pack=pack[k], half=half)
                if k in placed:
                    spec = placed[k].spec
                    # (check_vma off: the relayout's loop starts from
                    # zeros, which no mesh axis varies over)
                    fn = shard_map(fn, mesh=placed[k].mesh,
                                   in_specs=(spec,) * len(arrays),
                                   out_specs=spec, check_vma=False)
                return fn(*arrays)

            def every(half, *trees):
                return {k: leaf(k, half, *(t[k] for t in trees))
                        for k in trees[0]}

            return jax.jit(every, static_argnums=0, donate_argnums=donate,
                           out_shardings=placed or None)

        # (half, leaves[, stores]) -> stores: a store that is given keeps
        # its other half and is written in place
        self._fuse_j = relayout(sparse_kernels.lane_fused, donate=(2,))
        # (half, stores) -> logical leaves
        self._unfuse_j = relayout(sparse_kernels.lane_unfused)

    def _own(self, params):
        """The base's copy under the caller's shardings, except that a
        table kept in a fused store is copied INTO it, its accumulator
        lanes zero — the relayout is the copy and the fresh accumulator
        at once, so the constructor's peak stays the caller's table and
        the state."""
        pack = self._lane_pack
        if not pack:
            return super()._own(params)
        own = super()._own({k: v for k, v in params.items() if k not in pack})
        mine = {k: params[k] for k in pack}
        if isinstance(self._param_sharding, dict):
            # (no copy where the caller placed the table as the trainer will)
            mine = jax.device_put(
                mine, {k: self._param_sharding[k] for k in pack})
        return {**own, **self._fuse_j(0, mine)}

    def _unfused(self, half: int) -> Dict:
        """``{table: [V, d]}``: the tables (``half`` 0) or the accumulators
        (1) of the fused stores, logical.  Made anew on every call and
        never kept: at 2^25 x 32 one is 4.3 GB beside the state."""
        if not self._lane_pack:
            return {}
        return self._unfuse_j(
            half, {k: self._params[k] for k in self._lane_pack})

    def _refuse(self, half: int, leaves: Dict) -> None:
        """The fused stores with the logical ``leaves`` as their ``half``,
        the other half as it was (in place: the old stores are donated)."""
        pack = self._lane_pack
        if pack:
            self._params = {**self._params, **self._fuse_j(
                half, {k: leaves[k] for k in pack},
                {k: self._params[k] for k in pack})}

    @property
    def params(self):
        """The parameters with every table logical ``[V, d]`` (what the
        checkpointer, ``predict`` / ``evaluate`` and every tool read).
        ``train_step`` reads and writes ``_params``, the stored form."""
        return {**self._params, **self._unfused(0)}

    @params.setter
    def params(self, tree) -> None:
        self._params = {
            **{k: v for k, v in tree.items() if k not in self._lane_pack},
            **{k: self._params[k] for k in self._lane_pack}}
        self._refuse(0, tree)

    @property
    def opt_state(self):
        """The optimizer state with a logical ``[V, d]`` accumulator for
        every table, the ones a fused store holds read out of it."""
        state = self._opt_state
        return {**state, "accum": {**state["accum"], **self._unfused(1)}}

    @opt_state.setter
    def opt_state(self, tree) -> None:
        self._opt_state = {**tree, "accum": {
            k: v for k, v in tree["accum"].items()
            if k not in self._lane_pack}}
        self._refuse(1, tree["accum"])

    def _init_opt_state(self, params):
        """Dense leaves get optax state; table leaves get per-row Adagrad
        accumulators only (never the transient full-size optax state) —
        but for a table in a fused store, whose accumulator is lanes of
        ``params[table]`` and was zeroed with it (:meth:`_own`).
        With ``compress_bits`` the dense-ring EF residual carry rides along
        (CTRTrainer's CompressedRingState, flattened into this dict); with
        a FIXED float ``compress_range`` each table additionally carries a
        per-member ``[n, vocab, ...]`` sparse EF residual
        (``dist.collectives.sparse_ef_residual_init`` layout) so clipped
        sparse payload mass is delivered late instead of lost.  NOTE the
        memory cost: n x table size per table — fixed-range clipping plus
        EF is a deliberate bandwidth/memory trade (the default dynamic
        range needs neither)."""
        dense = {k: v for k, v in params.items() if k not in self._spec}
        state = {
            "dense": self.tx.init(dense),
            "accum": {
                k: jnp.zeros_like(params[k]) for k in self._spec
                if k not in self._lane_pack
            },
        }
        if self.compress_bits is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            n = self.mesh.shape["data"]
            residual = jnp.zeros(
                (n, self._ring_pad if self.error_feedback else 1),
                jnp.float32,
            )
            state["residual"] = jax.device_put(
                residual, NamedSharding(self.mesh, P("data"))
            )
        if self._use_sparse_ef():
            from jax.sharding import NamedSharding, PartitionSpec as P

            from lightctr_tpu.dist.collectives import sparse_ef_residual_init

            state["sres"] = {
                k: jax.device_put(
                    sparse_ef_residual_init(self.mesh, params[k].shape),
                    NamedSharding(self.mesh, P("data")),
                )
                for k in self._spec
            }
        return state

    # -- step --------------------------------------------------------------

    def _build_step(self):
        """Single-device and GSPMD-sharded configurations keep the one-
        program O(touched) step; replicated data parallelism takes the
        explicit hybrid exchange."""
        if self._hybrid_dp:
            return self._make_hybrid_dp_step()
        return self._make_step()

    @staticmethod
    def _field_groups(spec) -> Dict[tuple, list]:
        """{field_tuple: [table, ...]} in spec order — tables whose field
        lists concatenate to the SAME id stream share one dedup (and, in
        the hybrid exchange, one wire id stream)."""
        groups: Dict[tuple, list] = {}
        for k, fields in spec.items():
            groups.setdefault(tuple(fields), []).append(k)
        return groups

    def _row_shards(self) -> Dict[str, str]:
        """{table: mesh axis} for the tables whose rows ``param_shardings``
        shards over one mesh axis of more than one device: there the
        gather and the apply run per shard (docs/KERNELS.md, "On a mesh").
        Read off the sharding, so no mesh, a replicated table or an axis
        of size 1 gives ``{}`` and the step traces as on one device."""
        if self.mesh is None or not isinstance(self._param_sharding, dict):
            return {}
        out = {}
        for k in self._spec:
            spec = getattr(self._param_sharding.get(k), "spec", None)
            axis = spec[0] if spec else None
            if isinstance(axis, str) and self.mesh.shape[axis] > 1:
                out[k] = axis
        return out

    @staticmethod
    def _dedup_and_gather(spec, params, batch, mesh=None, row_shards=None,
                          lane_pack=None):
        """Steps 1-3 of the module recipe: per-table batch-id dedup,
        position rewrite, and the O(touched) row gather — of the live
        prefix of the slots, on the apply's ladder
        (``sparse_kernels.gather_live``); a table in ``row_shards``
        (``{table: axis}`` of ``mesh``) gathers each shard's own rows on
        the shard's own rung (``sparse_kernels.gather_shards``); a table
        in ``lane_pack`` (``{table: r}``) is a fused store ``[2V // r,
        128]`` gathered by whole lane rows — ``rows[k]`` is ``[K, d]`` all
        the same.  Shared by the single-program step and the per-replica
        hybrid step (where ``batch`` is the replica's local shard).  The
        last output is each id stream's distinct count, ``{field_tuple:
        count}``, as the dedup returns it.

        Tables listing the IDENTICAL field tuple run the dedup once and
        share the resulting ``(uids, inv)`` — their position rewrites
        coincide by construction (the __init__ overlap check guarantees
        no other sharing shape exists), so dedup FLOPs are paid per
        distinct id stream, not per table.  The dedup itself is
        ``ops.sparse_kernels.dedup_ids``: three sorts with payloads and a
        scan under the ``jnp.unique`` contract (docs/KERNELS.md)."""
        from lightctr_tpu.ops import sparse_kernels

        tables = {k: params[k] for k in spec}
        dense = {k: v for k, v in params.items() if k not in spec}
        batch2 = dict(batch)
        uids, counts = {}, {}
        groups = SparseTableCTRTrainer._field_groups(spec)

        def gather(k):
            axis = row_shards.get(k) if row_shards else None
            pack = lane_pack.get(k, 1) if lane_pack else 1
            if axis is None:
                return sparse_kernels.gather_live(
                    tables[k],
                    *sparse_kernels.live_plan(
                        uids[k],
                        tables[k].shape[0] * sparse_kernels.fused_ids(pack)),
                    pack=pack)
            return shard_map(
                partial(sparse_kernels.gather_shards, axis_name=axis,
                        pack=pack),
                mesh=mesh, in_specs=(P(axis), P()), out_specs=P(),
            )(tables[k], uids[k])

        with annotate("sparse_tables/dedup_gather", tables=len(spec),
                      id_streams=len(groups)):
            with annotate("dedup_ids"):
                for fields, keys in groups.items():
                    ids = jnp.concatenate(
                        [batch[f].reshape(-1) for f in fields]
                    ).astype(jnp.int32)
                    u, inv, counts[fields] = sparse_kernels.dedup_ids(ids)
                    for k in keys:
                        uids[k] = u
                    ofs = 0
                    for f in fields:
                        m = batch[f].size
                        batch2[f] = inv[ofs:ofs + m].reshape(batch[f].shape)
                        ofs += m
            # the forward gather reads the live prefix of the slots, on
            # the apply's ladder; the rows behind the rung are zeros, and
            # ``inv`` never points past the live prefix
            with annotate("gather_rows"):
                rows = {k: gather(k) for k in spec}
        return tables, dense, batch2, uids, rows, counts

    def _make_step(self):
        armed = self._quality_bins is not None
        loss_fn = self._make_loss_fn(with_probs=armed)
        tx = self.tx
        spec = self._spec
        lr, eps = self.cfg.learning_rate, self._eps
        dedup_and_gather = self._dedup_and_gather
        mesh, row_shards = self.mesh, self._row_shards()
        lane_pack = self._lane_pack
        # the softmax loss returns its counts beside the loss, as the
        # quality sketch's probabilities ride (never both: the ctor)
        seq = self.cfg.loss == "softmax_xent"
        has_aux = armed or seq
        vocab = {k: self._table_shapes[k][0] for k in spec}
        # the ``data`` replicas of a mesh hold their own rows of the batch
        # each: over more than one the step joins their row gradients
        # itself (``per_replica`` below)
        data_axis = "data"
        replicas = 1 if mesh is None else mesh.shape.get(data_axis, 1)
        # the joins a table's [K, ...] rows cross the mesh by: the sharded
        # forward gather's, and the row gradients' over ``data``
        joins = {k: (int(np.prod(self._table_shapes[k][1:]))
                     * self._params[k].dtype.itemsize,
                     ("rows_join",) * (k in row_shards)
                     + ("grad_join",) * (replicas > 1)) for k in spec}
        layout = _StepCounts(
            spec, vocab,
            {k: mesh.shape[axis] for k, axis in row_shards.items()},
            lane_pack,
            softmax_count_names(self.logits_fn) if seq else (), joins)
        if not self._hier:
            # (the hier trainer builds this program and never runs it)
            self._step_counts = layout

        def apply(k, table, accum, u, g):
            """Touched-row apply (``sparse_kernels.merge_apply``):
            sparse_adagrad_update's arithmetic over the live prefix of
            uids (already sorted unique; padded id-0 repeats carry zero
            gradient and are dropped).  Row-sharded tables apply per
            shard: each its own rows, on its own rung, every ``data``
            replica of a shard alike; a fused store (``accum`` None: it
            holds its own) by one gather and one scatter of whole lane
            rows."""
            from lightctr_tpu.ops import sparse_kernels

            axis = row_shards.get(k)

            def one(table, accum, u, g):
                return sparse_kernels.merge_apply(
                    table, accum, u, g, None, lr=lr, eps=eps,
                    shard_axis=axis, pack=lane_pack.get(k, 1))[:2]

            if axis is not None:
                one = shard_map(one, mesh=mesh,
                                in_specs=(P(axis), P(axis), P(), P()),
                                out_specs=(P(axis), P(axis)))
            return one(table, accum, u, g)

        def loss_and_grads(rows, dense, batch2, share=None):
            """``loss, aux, (g_rows, g_dense)`` of the loss over
            ``batch2`` (``aux``: the armed step's probabilities, the
            softmax loss's counts, else None), the loss times
            ``share(batch2)`` where a replica holds a share of the
            batch."""
            def loss_on(rows, dense):
                out = loss_fn({**dense, **rows}, batch2)
                loss, aux = out if has_aux else (out, None)
                if share is not None:
                    loss = loss * share(batch2)
                return loss, aux

            (loss, aux), grads = jax.value_and_grad(
                loss_on, argnums=(0, 1), has_aux=True)(rows, dense)
            return loss, aux, grads

        def per_replica(rows, dense, batch2, uids):
            """:func:`loss_and_grads` inside a ``shard_map`` over the
            mesh: ``batch2`` is one ``data`` replica's rows of the batch,
            everything else replicated (the ``embed`` members of a replica
            repeat its work).  Each replica differentiates its share of
            the global loss with respect to its own copy of the gathered
            rows, so the row gradients leave the model as PARTIAL ``[K,
            ...]`` sums, and the step joins them itself, flat and at the
            rung of the live prefix (``sparse_kernels.join_live``; ``inv``
            never points behind the prefix, so the rows behind it are
            zeros on every replica) — where GSPMD all-reduces all K rows
            of an ``[K, 32]`` operand that XLA:TPU pads to 128 lanes
            (docs/KERNELS.md, "On a mesh").  The join is the expansion's:
            it sums what the transposes of the model's ``rows[inv]``
            takes made.  The arithmetic is the one program's: the same
            partial sums on the same replicas, added over the same axis.

            ``check_vma`` is off: a model's ``lax.scan`` may start its
            carry from a constant, which the check refuses once the body
            makes it vary; unchecked, a gradient with respect to a
            replicated input is the replica's own partial sum, which is
            what the join takes."""
            from lightctr_tpu.ops import sparse_kernels

            loss, aux, (g_rows, g_dense) = loss_and_grads(
                rows, dense, batch2,
                share=partial(self._replica_share, axis=data_axis))
            with annotate("model/expand"):
                g_rows = {k: sparse_kernels.join_live(
                    g_rows[k], uids[k], data_axis, vocab[k]) for k in spec}
            with annotate("step/update"):
                # the loss and the dense gradients cross as one flat vector
                # (a 2-D operand under 128 lanes would be padded, as above)
                flat, unravel = ravel_pytree((loss, g_dense))
                loss, g_dense = unravel(jax.lax.psum(flat, data_axis))
                if seq:
                    aux = jax.lax.psum(aux, data_axis)
            return loss, aux, (g_rows, g_dense)

        if replicas > 1:
            loss_and_grads_of = shard_map(
                per_replica, mesh=mesh,
                in_specs=(P(), P(), P(data_axis), P()),
                # the probabilities stay with their rows of the batch
                out_specs=(P(), P(data_axis) if armed else P(), P()),
                check_vma=False)
        else:
            def loss_and_grads_of(rows, dense, batch2, uids):
                return loss_and_grads(rows, dense, batch2)

        def step(params, opt_state, batch):
            tables, dense, batch2, uids, rows, distinct = dedup_and_gather(
                spec, params, batch, mesh, row_shards, lane_pack
            )
            loss, aux, (g_rows, g_dense) = loss_and_grads_of(
                rows, dense, batch2, uids)
            probs, model_counts = (aux, None) if armed else (None, aux)
            with annotate("step/update"):
                # grad global norm over touched rows + dense leaves: the
                # health scalar (one reduction; fetched only when monitored)
                gnorm = optax.global_norm((g_rows, g_dense))

                updates, new_dense_state = tx.update(
                    g_dense, opt_state["dense"], dense)
                dense = jax.tree_util.tree_map(
                    lambda p, u: p + u.astype(p.dtype), dense, updates
                )

            new_accum = {}
            with annotate("sparse_tables/apply"):
                for k in spec:
                    tables[k], accum = apply(
                        k, tables[k], opt_state["accum"].get(k), uids[k],
                        g_rows[k])
                    if accum is not None:
                        new_accum[k] = accum

            params = {**dense, **tables}
            # (the scope a second time: the step's ops keep their order)
            with annotate("step/update"):
                health = self._append_sketch(
                    jnp.concatenate([_health_pack(loss, gnorm),
                                     layout.pack(distinct, uids, batch,
                                                 model_counts)]),
                    probs, batch2)
            return (params, {"dense": new_dense_state, "accum": new_accum},
                    loss, health)

        return step

    def _make_hybrid_dp_step(self):
        """Replicated data-parallel step with the hybrid explicit exchange
        (module docstring): per-replica O(touched) grads, table leaves over
        the three-way-picked sparse exchange (allgather ``sparse_all_reduce``,
        the owner-partitioned reduce-scatter variant, or the dense ring past
        the density switch), dense leaves over the compressed ring / psum
        mean.  One shard_map program — jit it whole, exactly like
        CTRTrainer's compressed step.  Tables sharing a field tuple share
        the exchanged ID stream: the id plumbing (gather / owner partition /
        shard merge) runs once per (stream, algo) group and only the first
        table of a group pays the wire id bytes."""
        from jax.sharding import PartitionSpec as P

        from jax import shard_map
        from lightctr_tpu.dist.collectives import (
            _ag_exchange_rows,
            _ag_gather_ids,
            _ring_all_reduce_local,
            _rs_merge_ids,
            _rs_ring_exchange,
            _rs_gather_rows,
            dense_ring_bytes,
            pick_exchange_algo,
            rs_default_caps,
            rs_owner_partition,
            sparse_exchange_bytes,
            sparse_rs_bytes,
        )
        from lightctr_tpu.ops import sparse_kernels

        armed = self._quality_bins is not None
        loss_fn = self._make_loss_fn(with_probs=armed)
        tx = self.tx
        spec = self._spec
        lr, eps = self.cfg.learning_rate, self._eps
        dedup_and_gather = self._dedup_and_gather
        groups = self._field_groups(spec)
        mesh = self.mesh
        n = mesh.shape["data"]
        bits = self.compress_bits
        crange, cmode = self.compress_range, self.compress_mode
        use_ef = self.error_feedback
        sparse_ef = self._use_sparse_ef()
        ring_pad = self._ring_pad if bits is not None else 0
        margin = self._dense_margin
        force_ag = self._force_ag
        # written at trace time; the overflow-fallback program (force_ag)
        # records into its own dicts so a traced fallback cannot shadow the
        # primary program's decisions
        if force_ag:
            policy = self._fallback_policy
            xbytes = self._fallback_bytes
        else:
            policy = self.exchange_policy
            xbytes = self.exchange_bytes_per_step

        def dense_table_exchange(g):
            """SparCML's switch-over target: the table gradient as one
            dense buffer over the (optionally quantized) ring.  No EF on
            this path — it is the worst-case escape hatch; its quantized
            form matches the plain compressed ring's 16-bit-grade use."""
            if bits is None:
                return jax.lax.pmean(g, "data")
            flat = g.reshape(-1)
            length = flat.shape[0]
            padded = ((length + n - 1) // n) * n
            if padded != length:
                flat = jnp.pad(flat, (0, padded - length))
            flat = _ring_all_reduce_local(
                flat, "data", n, average=True,
                compress_bits=bits, compress_range=crange,
                compress_mode=cmode,
            )
            return flat[:length].reshape(g.shape)

        def local_step(params, opt_state, batch):
            # batch arrives as this replica's shard: the dedup below is
            # per-replica, over O(local touched) ids
            tables, dense, batch2, uids, rows, _ = dedup_and_gather(
                spec, params, batch
            )

            def loss_on(rows, dense):
                return loss_fn({**dense, **rows}, batch2)

            if armed:
                (loss, probs), (g_rows, g_dense) = jax.value_and_grad(
                    loss_on, argnums=(0, 1), has_aux=True
                )(rows, dense)
            else:
                loss, (g_rows, g_dense) = jax.value_and_grad(
                    loss_on, argnums=(0, 1)
                )(rows, dense)
                probs = None
            # replica losses are local means; their mean is the global mean
            loss = jax.lax.pmean(loss, "data")

            # -- dense leaves: Parallax's ring half -------------------------
            new_res = opt_state["residual"][0] if bits is not None else None
            if bits is not None:
                flat, unravel = ravel_pytree(g_dense)
                length = flat.shape[0]
                if length:
                    if ring_pad != length:
                        flat = jnp.pad(flat, (0, ring_pad - length))
                    if use_ef:
                        flat, new_res = _ring_all_reduce_local(
                            flat, "data", n, average=True,
                            compress_bits=bits, compress_range=crange,
                            residual=new_res, compress_mode=cmode,
                        )
                    else:
                        flat = _ring_all_reduce_local(
                            flat, "data", n, average=True,
                            compress_bits=bits, compress_range=crange,
                            compress_mode=cmode,
                        )
                    g_dense = unravel(flat[:length])
            else:
                g_dense = jax.tree_util.tree_map(
                    lambda g: jax.lax.pmean(g, "data"), g_dense
                )

            # post-exchange gradients are replica-identical, so the norm
            # accumulated below is too (health scalar, out_specs P())
            gn2 = optax.global_norm(g_dense) ** 2

            updates, new_dense_state = tx.update(
                g_dense, opt_state["dense"], dense
            )
            dense = jax.tree_util.tree_map(
                lambda p, u: p + u.astype(p.dtype), dense, updates
            )

            # -- table leaves: three-way pick per table, id streams shared
            # within each (field-tuple, algo) group ------------------------
            new_accum = {}
            # per-table sparse EF carries (fixed-range clipped payloads):
            # allgather tables compensate through _ag_exchange_rows,
            # reduce-scatter tables through _rs_gather_rows' stage-1
            # carry; dense-ring tables pass theirs through untouched
            # (the dense ring is the worst-case escape hatch)
            new_sres = {}
            # in-jit rs overflow tally: the host-side rs_fits check should
            # make this identically zero, but if the two ever disagree the
            # count rides the health vector (third slot) instead of being
            # silent gradient loss — _observe_scalars surfaces it
            over_total = jnp.zeros((), jnp.int32)

            def apply_sparse(k, gu, rows, inv=None, denom=1.0):
                # identical (gu, rows) on every replica -> identical
                # update; duplicate slots merge inside the fused
                # merge-apply kernel (allgather path: inv maps the raw
                # gathered rows; rs path: rows arrived merged owner-side,
                # inv=None), padded slots carry zero rows (no-op).  The
                # merged sum of squares feeds the health gradient norm
                # from the same pass.
                with annotate("sparse_tables/apply"):
                    tables[k], new_accum[k], ssq = sparse_kernels.merge_apply(
                        tables[k],
                        opt_state["accum"][k],
                        gu,
                        rows,
                        inv,
                        lr=lr,
                        eps=eps,
                        denom=denom,
                    )
                return ssq

            for fields, keys in groups.items():
                u = uids[keys[0]]
                kpad = u.shape[0]
                # static trace-time pick per table, then share the id
                # plumbing within each (algo, caps) subgroup
                sub: Dict = {}
                for k in keys:
                    vocab = tables[k].shape[0]
                    dim = int(np.prod(tables[k].shape[1:]))
                    algo, _ = pick_exchange_algo(
                        n, kpad, vocab, dim,
                        sparse_bits=bits, dense_bits=bits, margin=margin,
                    )
                    if force_ag and algo == "sparse_rs":
                        # the overflow-fallback program: this batch's ids
                        # exceed the rs capacities, allgather stays exact
                        algo = "sparse"
                    caps = (rs_default_caps(n, kpad, vocab)
                            if algo == "sparse_rs" else None)
                    sub.setdefault((algo, caps), []).append(k)
                for (algo, caps), ks in sub.items():
                    if algo == "dense":
                        for k in ks:
                            vocab = tables[k].shape[0]
                            dim = int(np.prod(tables[k].shape[1:]))
                            policy[k] = "dense"
                            xbytes[k] = dense_ring_bytes(vocab, dim, n, bits)
                            with annotate("sparse_tables/dense_exchange",
                                          table=k):
                                g = jnp.zeros_like(tables[k]).at[uids[k]].add(
                                    g_rows[k]
                                )
                                g = dense_table_exchange(g)
                            gn2 = gn2 + jnp.sum(g * g)
                            # dense elementwise Adagrad without state decay
                            # — the same trajectory as the sparse recipe
                            # (untouched rows have g == 0: neither weights
                            # nor accum move)
                            with annotate("sparse_tables/apply"):
                                acc = opt_state["accum"][k] + g * g
                                tables[k] = tables[k] - lr * g * \
                                    jax.lax.rsqrt(acc + eps)
                            new_accum[k] = acc
                    elif algo == "sparse":
                        with annotate("sparse_tables/sparse_exchange",
                                      tables=len(ks)):
                            _, uniq, inv = _ag_gather_ids(u, "data")
                        for i, k in enumerate(ks):
                            dim = int(np.prod(tables[k].shape[1:]))
                            policy[k] = "sparse"
                            xbytes[k] = sparse_exchange_bytes(
                                n, kpad, dim, bits, include_ids=(i == 0)
                            )
                            with annotate("sparse_tables/sparse_exchange",
                                          table=k):
                                all_rows, nres = _ag_exchange_rows(
                                    g_rows[k], "data",
                                    compress_bits=bits,
                                    compress_range=(crange if bits is not None
                                                    else 1.0),
                                    compress_mode=cmode,
                                    uids=u if sparse_ef else None,
                                    residual=(opt_state["sres"][k][0]
                                              if sparse_ef else None),
                                )
                                if sparse_ef:
                                    new_sres[k] = nres[None]
                            # merge folded into the fused apply: the
                            # gathered gradient rows are read once —
                            # never materialized merged-then-applied
                            gn2 = gn2 + apply_sparse(
                                k, uniq, all_rows, inv=inv, denom=float(n)
                            )
                    else:  # sparse_rs
                        bucket_cap, shard_cap = caps
                        with annotate("sparse_tables/rs_exchange",
                                      tables=len(ks), bucket_cap=bucket_cap,
                                      shard_cap=shard_cap):
                            dest, order, bucket_ids, ov_b = \
                                rs_owner_partition(u, n, bucket_cap)
                            all_ids = _rs_ring_exchange(bucket_ids, "data", n)
                            uniq, inv, ov_s = _rs_merge_ids(
                                all_ids, shard_cap
                            )
                            over_total = over_total + ov_b + ov_s
                            out_ids = jax.lax.all_gather(
                                uniq, "data", tiled=True
                            )
                        for i, k in enumerate(ks):
                            dim = int(np.prod(tables[k].shape[1:]))
                            policy[k] = "sparse_rs"
                            xbytes[k] = sparse_rs_bytes(
                                n, bucket_cap, shard_cap, dim, bits,
                                include_ids=(i == 0),
                            )
                            with annotate("sparse_tables/rs_exchange",
                                          table=k):
                                out_rows = _rs_gather_rows(
                                    g_rows[k], dest, order, inv, "data", n,
                                    bucket_cap, shard_cap, average=True,
                                    compress_bits=bits,
                                    compress_range=(crange if bits is not None
                                                    else 1.0),
                                    compress_mode=cmode,
                                    uids=u if sparse_ef else None,
                                    residual=(opt_state["sres"][k][0]
                                              if sparse_ef else None),
                                )
                                if sparse_ef:
                                    out_rows, nres = out_rows
                                    new_sres[k] = nres[None]
                            # rows arrived merged owner-side: apply-only
                            # fused pass (inv=None)
                            gn2 = gn2 + apply_sparse(k, out_ids, out_rows)

            params = {**dense, **tables}
            new_state = {"dense": new_dense_state, "accum": new_accum}
            if bits is not None:
                new_state["residual"] = new_res[None]
            if sparse_ef:
                for k in spec:
                    if k not in new_sres:
                        # dense-ring tables (the worst-case escape
                        # hatch): the carry passes through untouched
                        new_sres[k] = opt_state["sres"][k]
                new_state["sres"] = new_sres
            # health vector gains a third slot: the cross-member rs
            # overflow count (psum -> replica-identical, like the rest).
            # Scan paths DCE it with the vector; the train_step feed
            # surfaces any nonzero count (trainer_rs_overflow_total).
            health = jnp.concatenate([
                _health_pack(loss, jnp.sqrt(gn2)),
                jax.lax.psum(over_total, "data").astype(jnp.float32)[None],
            ])
            health = self._append_sketch(health, probs, batch2, axis="data")
            return params, new_state, loss, health

        state_spec = {"dense": P(), "accum": {k: P() for k in spec}}
        if bits is not None:
            state_spec["residual"] = P("data")
        if sparse_ef:
            state_spec["sres"] = {k: P("data") for k in spec}
        return shard_map(
            local_step,
            mesh=mesh,
            in_specs=(P(), state_spec, P("data")),
            out_specs=(P(), state_spec, P(), P()),
            check_vma=False,
        )

    # -- hierarchical two-level exchange (docs/SPARSE_EXCHANGE.md) -------
    #
    # Three pieces per step: (A) one jitted shard_map program computes
    # per-replica O(touched) grads and merges them ACROSS THE LOCAL MESH
    # in-jit (the ICI hop — replicated output, so the host reads ONE
    # merged (uids, rows) pair per id stream); (B) the host strips the
    # dedup padding and runs the wire rendezvous (the DCN hop: push this
    # host's merged sums, pull the cross-host merge — exactly one payload
    # per host, so cross-host bytes are flat in local replica count); (C)
    # a second jitted program applies the identical global mean on every
    # replica (merge_apply with pre-merged rows) — replicas cannot
    # diverge, and with every host applying the same update neither can
    # hosts.  The trajectory equals the dense-psum data-parallel trainer
    # over the GLOBAL batch (the 2-process acceptance test's oracle).

    #: wire table id of the dense-leaf stream: dense gradients flatten to
    #: one [L] vector and ride the same rendezvous as dim-1 rows keyed by
    #: position, with the replica-summed loss appended as the last entry
    #: (the cross-host loss mean needs a wire hop anyway — it shares this
    #: one).  Real tables use ids 0..len(spec)-1 in spec order.
    _HIER_DENSE_TABLE = 1 << 20

    def _make_hier_local_step(self):
        """Program A: per-replica grads + the in-jit LOCAL merge (SUM over
        local replicas, never averaged — the global denominator is applied
        after the wire merge).  Per id stream the merge rides the cheaper
        of the two sparse collectives (``self._force_ag`` pins the
        allgather for the overflow-fallback program family); the dense
        leaves and the loss psum into one flat vector.  Every output is
        replica-identical (terminal collectives), so the shard_map emits
        replicated values the host reads once."""
        from jax.sharding import PartitionSpec as P

        from jax import shard_map
        from lightctr_tpu.dist.collectives import (
            _ag_exchange_rows,
            _ag_gather_ids,
            _rs_merge_ids,
            _rs_ring_exchange,
            _rs_gather_rows,
            rs_owner_partition,
        )
        from lightctr_tpu.ops import sparse_kernels

        armed = self._quality_bins is not None
        loss_fn = self._make_loss_fn(with_probs=armed)
        spec = self._spec
        groups = self._field_groups(spec)
        mesh = self.mesh
        n = mesh.shape["data"]
        dedup_and_gather = self._dedup_and_gather
        force_ag = self._force_ag
        if force_ag:
            policy, xbytes = self._hier_fb_local_policy, \
                self._hier_fb_local_bytes
        else:
            policy, xbytes = self.hier_local_policy, \
                self.hier_local_bytes_per_step

        def local_step(params, batch):
            tables, dense, batch2, uids, rows, _ = dedup_and_gather(
                spec, params, batch
            )

            def loss_on(rows, dense):
                return loss_fn({**dense, **rows}, batch2)

            if armed:
                (loss, probs), (g_rows, g_dense) = jax.value_and_grad(
                    loss_on, argnums=(0, 1), has_aux=True
                )(rows, dense)
            else:
                loss, (g_rows, g_dense) = jax.value_and_grad(
                    loss_on, argnums=(0, 1)
                )(rows, dense)
                probs = None
            # dense grads + the per-replica mean loss ride ONE flat psum:
            # [sum over local replicas of grads..., sum of losses]
            flat, _ = ravel_pytree(g_dense)
            dense_flat = jax.lax.psum(
                jnp.concatenate([flat, loss[None].astype(jnp.float32)]),
                "data",
            )
            over_total = jnp.zeros((), jnp.int32)
            out_ids: Dict = {}
            out_rows: Dict = {}
            for fields, keys in groups.items():
                u = uids[keys[0]]
                kpad = u.shape[0]
                vocab = max(tables[k].shape[0] for k in keys)
                dims = [int(np.prod(tables[k].shape[1:])) for k in keys]
                # the SAME comparison the host-side plan mirror makes —
                # caps and program family cannot drift (_hier_local_algo)
                algo, caps, per_bytes = _hier_local_algo(
                    n, kpad, vocab, dims, force_ag=force_ag
                )
                if algo == "sparse":
                    with annotate("sparse_tables/hier_local",
                                  algo="sparse", tables=len(keys)):
                        _, uniq, inv = _ag_gather_ids(u, "data")
                        for i, k in enumerate(keys):
                            policy[k] = "sparse"
                            xbytes[k] = per_bytes[i]
                            all_rows, _ = _ag_exchange_rows(g_rows[k], "data")
                            out_ids[k] = uniq
                            out_rows[k] = sparse_kernels.merge_rows(
                                all_rows, inv, uniq.shape[0]
                            )
                else:
                    bucket_cap, shard_cap = caps
                    with annotate("sparse_tables/hier_local",
                                  algo="sparse_rs", tables=len(keys)):
                        dest, order, bucket_ids, ov_b = \
                            rs_owner_partition(u, n, bucket_cap)
                        all_ids = _rs_ring_exchange(bucket_ids, "data", n)
                        uniq, inv, ov_s = _rs_merge_ids(all_ids, shard_cap)
                        over_total = over_total + ov_b + ov_s
                        ids_g = jax.lax.all_gather(uniq, "data", tiled=True)
                        for i, k in enumerate(keys):
                            policy[k] = "sparse_rs"
                            xbytes[k] = per_bytes[i]
                            out_ids[k] = ids_g
                            out_rows[k] = _rs_gather_rows(
                                g_rows[k], dest, order, inv, "data", n,
                                bucket_cap, shard_cap, average=False,
                            )
            over = jax.lax.psum(over_total, "data")
            if armed:
                # quality sketch over this HOST's global batch (psum over
                # the local mesh): rides the payload to program C, which
                # appends it to the health vector — the DCN hop never
                # sees it (each host's tracker covers its own stream; the
                # cluster rollup merges them)
                sketch = jax.lax.psum(
                    quality_mod.quality_sketch(
                        probs, batch2["labels"], self._quality_bins
                    ),
                    "data",
                )
                return out_ids, out_rows, dense_flat, over, sketch
            return out_ids, out_rows, dense_flat, over

        ospec = ({k: P() for k in spec}, {k: P() for k in spec}, P(), P())
        if armed:
            ospec = ospec + (P(),)
        return shard_map(
            local_step, mesh=mesh, in_specs=(P(), P("data")),
            out_specs=ospec, check_vma=False,
        )

    def _make_hier_apply_step(self):
        """Program C: apply the wire-merged GLOBAL MEAN on every replica —
        tables through the fused merge-apply (rows arrive pre-merged:
        ``inv=None``), dense leaves through optax, the merged sum of
        squares feeding the health gradient norm from the same passes.
        Identical inputs on every host => identical parameters
        everywhere."""

        tx = self.tx
        spec = self._spec
        lr, eps = self.cfg.learning_rate, self._eps
        armed = self._quality_bins is not None

        def _apply(params, opt_state, payload, dense_mean, loss, over,
                   sketch):
            from lightctr_tpu.ops import sparse_kernels

            tables = {k: params[k] for k in spec}
            dense = {k: v for k, v in params.items() if k not in spec}
            _, unravel = ravel_pytree(dense)
            g_dense = unravel(dense_mean)
            gn2 = optax.global_norm(g_dense) ** 2
            updates, new_dense_state = tx.update(
                g_dense, opt_state["dense"], dense
            )
            dense = jax.tree_util.tree_map(
                lambda p, u: p + u.astype(p.dtype), dense, updates
            )
            new_accum = {}
            with annotate("sparse_tables/apply"):
                for k in spec:
                    gu, grows = payload[k]
                    tables[k], new_accum[k], ssq = sparse_kernels.merge_apply(
                        tables[k], opt_state["accum"][k], gu, grows, None,
                        lr=lr, eps=eps,
                    )
                    gn2 = gn2 + ssq
            health = jnp.stack([
                loss, jnp.sqrt(gn2), over.astype(jnp.float32)
            ])
            if sketch is not None:
                health = jnp.concatenate([health, sketch])
            return ({**dense, **tables},
                    {"dense": new_dense_state, "accum": new_accum},
                    loss, health)

        if armed:
            def apply_step(params, opt_state, payload, dense_mean, loss,
                           over, sketch):
                return _apply(params, opt_state, payload, dense_mean,
                              loss, over, sketch)
        else:
            def apply_step(params, opt_state, payload, dense_mean, loss,
                           over):
                return _apply(params, opt_state, payload, dense_mean,
                              loss, over, None)

        return apply_step

    def _hier_local_plan(self, batch) -> Dict[str, tuple]:
        """Host-side mirror of the local step's per-stream algo choice —
        literally the same :func:`_hier_local_algo` call the traced
        program makes, cached per batch-shape signature, shaped like
        :meth:`_exchange_plan` so :meth:`_rs_batch_fits` (over the LOCAL
        mesh world) can consume it."""
        n = self.mesh.shape["data"]
        groups = self._field_groups(self._spec)
        sig = ("hier",) + tuple(
            (fields, tuple(tuple(np.shape(batch[f])) for f in fields))
            for fields in groups
        )
        plan = self._plan_cache.get(sig)
        if plan is not None:
            return plan
        plan = {}
        for fields, keys in groups.items():
            kpad = sum(
                int(np.prod(np.shape(batch[f]))) for f in fields
            ) // n
            vocab = max(self._table_shapes[k][0] for k in keys)
            dims = [int(np.prod(self._table_shapes[k][1:])) for k in keys]
            algo, caps, _ = _hier_local_algo(n, kpad, vocab, dims)
            for k in keys:
                plan[k] = (fields, algo, caps)
        self._plan_cache[sig] = plan
        return plan

    def _hier_local_ag(self):
        if self._hier_local_ag_j is None:
            self._force_ag = True
            try:
                self._hier_local_ag_j = jax.jit(self._make_hier_local_step())
            finally:
                self._force_ag = False
        return self._hier_local_ag_j

    @staticmethod
    def _hier_strip_plan(uids: np.ndarray):
        """The ONE copy of the wire-facing pad-strip convention ->
        ``(real mask, sort order over the real entries)``: drop id-0
        repeats beyond slot 0 — slot 0 survives whether id 0 is real or
        the conventional fill (a zero row there is a no-op on both the
        wire merge and the apply) — then sort globally (the
        reduce-scatter local merge emits per-owner-sorted shards).
        Tables sharing one id stream apply the same plan to each of
        their row payloads."""
        real = ~((uids == 0) & (np.arange(len(uids)) > 0))
        order = np.argsort(uids[real], kind="stable")
        return real, order

    @staticmethod
    def _hier_strip_pads(uids: np.ndarray, rows: np.ndarray):
        """Collapse a dedup-convention (uids, rows) pair to its real
        entries, globally sorted (:meth:`_hier_strip_plan`)."""
        real, order = SparseTableCTRTrainer._hier_strip_plan(uids)
        return uids[real][order], rows[real][order]

    @staticmethod
    def _hier_pad(uids: np.ndarray, rows: np.ndarray):
        """Pad a sorted-unique wire result back into the dedup convention
        at the next power of two (bounded jit-shape family for the apply
        program): id-0 fill, zero rows."""
        m = len(uids)
        size = 1 << max(3, (max(m, 1) - 1).bit_length())
        u = np.zeros(size, np.int32)
        u[:m] = uids.astype(np.int32)
        r = np.zeros((size,) + rows.shape[1:], np.float32)
        r[:m] = rows
        return u, r

    def _hier_step(self, params, opt_state, batch):
        """The per-step orchestrator ``self._step`` points at in hier
        mode: program A (local merge) -> the wire rendezvous -> program C
        (apply the global mean).  The local reduce-scatter capacities are
        expected sizes with slack, so every batch is checked host-side
        first and a would-overflow batch runs the allgather local-merge
        program instead — every branch stays exact.

        The wire hop groups tables by batch-field tuple: tables sharing
        one id stream produce the identical merged union, so their uids
        ride the wire ONCE per (host, group) via the client's grouped
        frames (push_group/pull_group) — the socket twin of the in-jit
        shared streams.  The dense+loss pseudo-table always rides exact
        fp32 whatever the codec (the loss readout must not wobble)."""
        from lightctr_tpu.dist.collectives import hier_wire_bytes

        import time as _time

        client = self._hier_client
        n_local = self.mesh.shape["data"]
        total = n_local * client.n_hosts
        wire_bits = {"f32": None, "f16": 16, "q8_ef": 8,
                     "q4_ef": 4}[client.codec]
        epoch = self._hier_epoch
        self._hier_epoch += 1

        plan = self._hier_local_plan(batch)
        fits = self._rs_batch_fits(batch, plan)
        self._hier_last_local = not fits
        if fits:
            local = self._hier_local_j
        else:
            self.telemetry.inc("trainer_rs_fallback_total")
            local = self._hier_local_ag()
        if self._quality_bins is not None:
            # the sketch stays a DEVICE array end to end: program A ->
            # program C, appended to the health vector there — the
            # orchestrator never fetches it
            if self.device is not None:
                self.device.offer("hier_local_step", local, (params, batch))
            out_ids, out_rows, dense_flat, over, sketch = local(params, batch)
        else:
            if self.device is not None:
                self.device.offer("hier_local_step", local, (params, batch))
            out_ids, out_rows, dense_flat, over = local(params, batch)
            sketch = None

        # -- the DCN hop: one merged payload per host.  All groups PUSH
        # before any pull: each round's barrier is crossed while later
        # groups' payloads are already in flight, so a step pays ~one
        # rendezvous round trip, not one per table --------------------------
        payload = {}
        table_id = {k: ti for ti, k in enumerate(self._hier_tables)}
        groups = self._field_groups(self._spec)
        sock0 = client.bytes_sent + client.bytes_received
        saved0 = client.shared_id_saved_bytes
        chunk0 = (client.chunk_pushes_total, client.chunk_rows_total,
                  client.chunk_capacity_rows_total)
        fp32_equiv = 0
        sw = self.stepwatch
        if sw is not None:
            # the phase a stalled rendezvous wedges in: a stepwatch trip
            # while a pull is withheld names "exchange" by construction
            sw.mark("exchange")
        with annotate("sparse_tables/hier_wire", tables=len(self._spec),
                      epoch=epoch):
            # dispatch/commit overlap (ISSUE 16): every group's chunked
            # push is DISPATCHED to its stripe pipelines as its arrays
            # materialize — group k's frames transmit while group k+1's
            # device outputs force and strip on this thread — and one
            # commit joins them right before the first pull.  The commit
            # wall is the push time the overlap did NOT hide.
            t_dispatch0 = _time.perf_counter()
            pushed = []
            for fields, keys in groups.items():
                # one pad-strip/sort per GROUP (the stream's union is
                # shared); per-table rows ride the same permutation
                u = np.asarray(out_ids[keys[0]])
                real, order = self._hier_strip_plan(u)
                su = u[real][order]
                rows_g = [
                    np.asarray(out_rows[k]).reshape(len(u), -1)[real][order]
                    for k in keys
                ]
                tids = [table_id[k] for k in keys]
                dims = [r.shape[1] for r in rows_g]
                if len(keys) == 1:
                    client.push_async(tids[0], su, rows_g[0], epoch)
                else:
                    client.push_group_async(tids, su, rows_g, epoch)
                pushed.append((keys, tids, dims, len(su)))
            # dense leaves + loss: positions as dim-1 rows, exact fp32
            dvec = np.asarray(dense_flat, np.float32).reshape(-1, 1)
            client.push_async(self._HIER_DENSE_TABLE,
                              np.arange(len(dvec), dtype=np.int64), dvec,
                              epoch, exact=True)
            t_commit0 = _time.perf_counter()
            client.commit()
            t_done = _time.perf_counter()
            self._hier_push_seconds = t_done - t_dispatch0
            self._hier_blocked_seconds = t_done - t_commit0
            for keys, tids, dims, k_out in pushed:
                if len(keys) == 1:
                    g_u, rows_out = client.pull(tids[0], epoch, dims[0])
                    rows_out = [rows_out]
                else:
                    g_u, rows_out = client.pull_group(tids, epoch, dims)
                for i, k in enumerate(keys):
                    self.exchange_policy[k] = "hier"
                    # the byte model prices the coded codec at its real
                    # wire_bits and the shared stream's ids ONCE per
                    # group — the same accounting pick_exchange_algo uses
                    self.exchange_bytes_per_step[k] = hier_wire_bytes(
                        k_out, len(g_u), dims[i], wire_bits,
                        include_ids=(i == 0),
                    )
                    fp32_equiv += hier_wire_bytes(k_out, len(g_u), dims[i])
                    pu, pr = self._hier_pad(
                        g_u, rows_out[i].reshape(
                            (len(g_u),) + self._table_shapes[k][1:]
                        ) / total
                    )
                    payload[k] = (jnp.asarray(pu), jnp.asarray(pr))
            d_u, d_r = client.pull(self._HIER_DENSE_TABLE, epoch, 1,
                                   exact=True)
            self._hier_wire_dense_bytes = hier_wire_bytes(
                len(dvec), len(d_u), 1, None
            )
            fp32_equiv += self._hier_wire_dense_bytes
        # wire-codec honesty numbers for this step: measured socket bytes
        # vs the fp32-equivalent of the same payload, the id bytes the
        # shared streams did not ship, and the undelivered EF mass
        self._hier_wire_packed_bytes = (
            client.bytes_sent + client.bytes_received - sock0
        )
        self._hier_wire_fp32_bytes = fp32_equiv
        self._hier_wire_id_saved = client.shared_id_saved_bytes - saved0
        self._hier_chunk_pushes = client.chunk_pushes_total - chunk0[0]
        self._hier_chunk_rows = client.chunk_rows_total - chunk0[1]
        self._hier_chunk_capacity = (
            client.chunk_capacity_rows_total - chunk0[2]
        )
        dsum = d_r.reshape(-1) / total
        loss = float(dsum[-1])
        dense_mean = jnp.asarray(dsum[:-1], jnp.float32)

        if sw is not None:
            sw.mark("apply")
        apply_args = (params, opt_state, payload, dense_mean,
                      jnp.float32(loss), jnp.asarray(over))
        if sketch is not None:
            apply_args = apply_args + (sketch,)
        if self.device is not None:
            # the hier step itself is a host orchestrator; its two jitted
            # halves are the analyzable device programs
            self.device.offer("hier_apply_step", self._hier_apply_j,
                              apply_args)
        new_params, new_state, loss_out, health = self._hier_apply_j(
            *apply_args
        )
        del loss_out  # the host already holds the float
        return new_params, new_state, loss, health

    # -- reduce-scatter capacity plan / overflow fallback ---------------

    def _exchange_plan(self, batch) -> Dict[str, tuple]:
        """Host-side mirror of the trace-time pick: {table: (fields, algo,
        caps)} from static shapes — the SAME ``pick_exchange_algo`` /
        ``rs_default_caps`` calls the traced program makes, so host plan
        and compiled program cannot disagree.  Cached per batch field-shape
        signature."""
        from lightctr_tpu.dist.collectives import (
            pick_exchange_algo, rs_default_caps,
        )

        n = self.mesh.shape["data"]
        groups = self._field_groups(self._spec)
        sig = tuple(
            (fields, tuple(tuple(np.shape(batch[f])) for f in fields))
            for fields in groups
        )
        plan = self._plan_cache.get(sig)
        if plan is not None:
            return plan
        plan = {}
        for fields, keys in groups.items():
            kpad = sum(
                int(np.prod(np.shape(batch[f]))) for f in fields
            ) // n
            for k in keys:
                vocab = self._table_shapes[k][0]
                dim = int(np.prod(self._table_shapes[k][1:]))
                algo, _ = pick_exchange_algo(
                    n, kpad, vocab, dim,
                    sparse_bits=self.compress_bits,
                    dense_bits=self.compress_bits,
                    margin=self._dense_margin,
                )
                caps = (rs_default_caps(n, kpad, vocab)
                        if algo == "sparse_rs" else None)
                plan[k] = (fields, algo, caps)
        self._plan_cache[sig] = plan
        return plan

    def _rs_batch_fits(self, batch, plan) -> bool:
        """Exact host-side capacity check for this batch's reduce-scatter
        tables (numpy over the raw id streams — one unique pass per member
        per distinct stream, shared across that stream's cap combos).
        True when every rs (stream, caps) combo fits; False routes the
        batch to the allgather fallback program."""
        from lightctr_tpu.dist.collectives import rs_fits

        by_stream: Dict[tuple, set] = {}
        for fields, algo, caps in plan.values():
            if algo == "sparse_rs":
                by_stream.setdefault(fields, set()).add(caps)
        if not by_stream:
            return True
        n = self.mesh.shape["data"]
        for fields, cap_set in by_stream.items():
            per_member = [
                np.concatenate([
                    # each field shards by ITS OWN leading dim (fields of
                    # one tuple may have different axis-0 sizes)
                    np.asarray(batch[f])[
                        m * (np.shape(batch[f])[0] // n):
                        (m + 1) * (np.shape(batch[f])[0] // n)
                    ].reshape(-1)
                    for f in fields
                ])
                for m in range(n)
            ]
            for bucket_cap, shard_cap in cap_set:
                if not rs_fits(per_member, n, bucket_cap, shard_cap):
                    return False
        return True

    def _fallback_step_fn(self):
        if self._step_ag is None:
            self._force_ag = True
            try:
                self._step_ag = jax.jit(
                    self._make_hybrid_dp_step(), donate_argnums=(0, 1)
                )
            finally:
                self._force_ag = False
        return self._step_ag

    def _prefetch_prepare(self):
        # the exchange planner (_exchange_plan/_rs_batch_fits) inspects
        # HOST ids before dispatch, so a prefetch stage must hand this
        # trainer host batches: prefetch overlaps the parse/pad only and
        # the step keeps its own _put
        return None

    def train_step(self, batch, **kw):
        self._last_step_fallback = False
        if self._hybrid_dp:
            # host work on the batch's ids BEFORE the step: its own span,
            # ahead of the trainer/step it plans for
            with trace_mod.span("trainer/plan"):
                fits = self._rs_batch_fits(batch, self._exchange_plan(batch))
            if not fits:
                self._last_step_fallback = True
                self.telemetry.inc("trainer_rs_fallback_total")
                primary, self._step = self._step, self._fallback_step_fn()
                try:
                    return super().train_step(batch, **kw)
                finally:
                    self._step = primary
        return super().train_step(batch, **kw)

    def fit(self, arrays, epochs=None, batch_size=None, eval_arrays=None,
            eval_every=0, verbose=False, prefetch=None):
        # the full-batch epoch path dispatches self._step directly, so the
        # rs capacity check must happen here (minibatch fits go through
        # train_step, which guards itself)
        arrays = self._resolve_arrays(arrays)
        kw = dict(epochs=epochs, batch_size=batch_size,
                  eval_arrays=eval_arrays, eval_every=eval_every,
                  verbose=verbose, prefetch=prefetch)
        if (self._hybrid_dp and batch_size is None
                and not self._rs_batch_fits(arrays,
                                            self._exchange_plan(arrays))):
            self.telemetry.inc("trainer_rs_fallback_total")
            primary, self._step = self._step, self._fallback_step_fn()
            try:
                return super().fit(arrays, **kw)
            finally:
                self._step = primary
        return super().fit(arrays, **kw)

    def fit_fullbatch_scan(self, arrays, epochs):
        if self._hier:
            raise ValueError(
                "the hierarchical exchange steps through a host wire hop "
                "and cannot ride lax.scan; use fit()/train_step()"
            )
        if (self._hybrid_dp
                and not self._rs_batch_fits(arrays,
                                            self._exchange_plan(arrays))):
            self.telemetry.inc("trainer_rs_fallback_total")
            self._force_ag = True
            try:
                return super().fit_fullbatch_scan(arrays, epochs)
            finally:
                self._force_ag = False
        return super().fit_fullbatch_scan(arrays, epochs)

    def _get_scan_fn(self, epochs: int):
        if self._force_ag:
            # the fallback scan compiles against its own cache so the two
            # program families never collide under one epochs key
            main, self._scan_cache = self._scan_cache, self._scan_cache_ag
            try:
                return super()._get_scan_fn(epochs)
            finally:
                self._scan_cache = main
        return super()._get_scan_fn(epochs)

    # -- telemetry ------------------------------------------------------

    def _live_exchange_dicts(self):
        """(policy, bytes) dicts of the program that actually ran the last
        step — the fallback program records into its own pair."""
        if self._last_step_fallback:
            return self._fallback_policy, self._fallback_bytes
        return self.exchange_policy, self.exchange_bytes_per_step

    def _vector_signals(self) -> tuple:
        return ("table_touch",) if self._step_counts is not None else ()

    def _observe_scalars(self, hm, health) -> None:
        """What rides the vector behind ``[loss, grad_norm]`` is the step
        program's own: the one-program step's counts (``_StepCounts``:
        the skew detector's ``table_touch`` and the apply's counters,
        from the same single fetch, a queue's lag behind the step), or
        the hybrid/hier step's third slot, the in-jit rs overflow count.
        Nonzero means the host capacity check and the compiled program
        disagreed — gradient entries were dropped; surface it loudly
        instead of silently.  Anything past the head is the quality
        sketch (when armed), so the slots are addressed by what the
        program carries, not by length."""
        vals = self._fetch_health(health)
        signals = {"loss": float(vals[0]), "grad_norm": float(vals[1])}
        counts = self._step_counts
        if counts is not None:
            head = 2 + counts.width
            if hm is not None and hm.wants("table_touch"):
                signals["table_touch"] = counts.read(vals[2:head],
                                                     self.telemetry)
                self.telemetry.inc(obs.labeled(
                    "trainer_health_signals_total", source="device"))
            elif counts.model:
                counts.read(vals[2:head], self.telemetry, tables=False)
        else:
            head = 3
            if vals.shape[0] > 2 and vals[2] > 0:
                self.telemetry.inc("trainer_rs_overflow_total", int(vals[2]))
                obs.emit_event("rs_overflow", count=int(vals[2]))
        if hm is not None:
            hm.observe(**signals)
        self._feed_quality(vals, head)

    def _exchange_byte_totals(self):
        """(sparse_bytes, rs_bytes, dense_bytes) each member transmits per
        step under the trace-time decisions; populated after the first
        step."""
        policy, xbytes = self._live_exchange_dicts()
        sparse_b = rs_b = dense_b = 0
        for k, pol in policy.items():
            b = xbytes.get(k, 0)
            if pol == "sparse":
                sparse_b += b
            elif pol == "sparse_rs":
                rs_b += b
            else:
                dense_b += b
        return sparse_b, rs_b, dense_b

    def _step_event_fields(self) -> Dict:
        if self._hier and self.exchange_policy:
            _, wire_b, _ = self._hier_byte_totals()
            lb = (self._hier_fb_local_bytes if self._hier_last_local
                  else self.hier_local_bytes_per_step)
            return {
                "exchange_policy": dict(self.exchange_policy),
                "hier_wire_bytes": wire_b + self._hier_wire_dense_bytes,
                "hier_local_bytes": sum(lb.values()),
                "hier_local_fallback": self._hier_last_local,
            }
        if not (self._hybrid_dp and self._live_exchange_dicts()[0]):
            return {}
        sparse_b, rs_b, dense_b = self._exchange_byte_totals()
        policy, _ = self._live_exchange_dicts()
        return {
            "exchange_policy": dict(policy),
            "sparse_exchange_bytes": sparse_b,
            "sparse_rs_bytes": rs_b,
            "dense_ring_bytes": dense_b,
        }

    def _hier_byte_totals(self):
        """(per-table wire dict, wire total over tables, local total) of
        the last hier step."""
        wire = dict(self.exchange_bytes_per_step)
        lb = (self._hier_fb_local_bytes if self._hier_last_local
              else self.hier_local_bytes_per_step)
        return wire, sum(wire.values()), sum(lb.values())

    def _health_signals(self, batch) -> Dict:
        """Per-table touched-uid counts for the skew detector where the
        step's program does not carry them: the hybrid and hier programs
        dedup each replica's LOCAL rows in-jit, so the global distinct
        count is nowhere on the device, and the host counts it — the id
        columns fetched back and one ``np.unique`` a table, on the step's
        thread (5.8 ms a step at a Criteo-shape batch; PERF.md section 6,
        PR 35).  The one-program step returns its own dedup's counts in
        the health vector (:meth:`_observe_scalars`) and nothing is
        counted here.  Skipped entirely unless a table_skew detector is
        installed."""
        hm = self.health
        if (self._step_counts is not None or hm is None
                or not hm.wants("table_touch")):
            return {}
        touch = {}
        for k, fields in self._spec.items():
            ids = np.concatenate(
                [np.asarray(batch[f]).reshape(-1) for f in fields]
            )
            touch[k] = {
                "unique": int(np.unique(ids).size),
                "ids": int(ids.size),
                "vocab": self._table_shapes[k][0],
            }
        self.telemetry.inc(obs.labeled(
            "trainer_health_signals_total", source="host"))
        return {"table_touch": touch}

    def _record_step(self, dt: float, batch, health=None) -> None:
        super()._record_step(dt, batch, health=health)
        policy, xbytes = self._live_exchange_dicts()
        if not ((self._hybrid_dp or self._hier) and policy):
            return
        reg = self.telemetry
        for k, pol in policy.items():
            b = xbytes.get(k, 0)
            reg.inc(
                obs.labeled("trainer_exchange_bytes_total",
                            table=k, policy=pol),
                b,
            )
            # per-table algorithm counter: which exchange each table leaf
            # actually ran this step (the four-way pick, fallback included)
            reg.inc(obs.labeled("trainer_exchange_algo_total",
                                table=k, algo=pol))
            if pol == "sparse":
                reg.inc("trainer_sparse_exchange_bytes_total", b)
            elif pol == "sparse_rs":
                reg.inc("trainer_sparse_rs_bytes_total", b)
            elif pol == "hier":
                # per-hop accounting: the table's DCN wire bytes here, its
                # share of the ICI local-merge hop below
                reg.inc("trainer_hier_wire_bytes_total", b)
            else:
                reg.inc("trainer_dense_ring_bytes_total", b)
        if self._hier:
            # the dense+loss stream rides the wire once per step too, and
            # the local ICI merge hop has its own counter (the program
            # family that actually ran records its own byte dicts)
            reg.inc("trainer_hier_wire_bytes_total",
                    self._hier_wire_dense_bytes)
            lb = (self._hier_fb_local_bytes if self._hier_last_local
                  else self.hier_local_bytes_per_step)
            reg.inc("trainer_hier_local_bytes_total", sum(lb.values()))
            # wire-codec honesty (ISSUE 13): measured socket bytes vs the
            # fp32-equivalent of the identical payload, the id bytes the
            # shared streams saved, and the undelivered member-side EF
            # mass — metrics_report --exchange renders compression and
            # dedup ratios from exactly these
            reg.inc("trainer_hier_wire_packed_bytes_total",
                    self._hier_wire_packed_bytes)
            reg.inc("trainer_hier_wire_fp32_bytes_total",
                    self._hier_wire_fp32_bytes)
            reg.inc("trainer_hier_wire_id_saved_bytes_total",
                    self._hier_wire_id_saved)
            reg.gauge_set("trainer_hier_wire_ef_mass",
                          self._hier_client.carry_mass())
            # streaming-rendezvous overlap honesty (ISSUE 16): chunk fill
            # = rows/capacity (near-empty windows waste frame headers),
            # overlap ratio = 1 - blocked/push (how much of the push wall
            # the dispatch/commit ticket hid under compute)
            reg.inc("trainer_hier_chunk_pushes_total",
                    self._hier_chunk_pushes)
            reg.inc("trainer_hier_chunk_rows_total",
                    self._hier_chunk_rows)
            reg.inc("trainer_hier_chunk_capacity_rows_total",
                    self._hier_chunk_capacity)
            reg.inc("trainer_hier_overlap_push_seconds_total",
                    self._hier_push_seconds)
            reg.inc("trainer_hier_overlap_blocked_seconds_total",
                    self._hier_blocked_seconds)
        # the pick is static post-trace: one ``exchange`` event per table
        # per PROGRAM, not one per step.  Primary and fallback decisions
        # log independently (a fallback first step must not be
        # immortalized as the run's choice, and a run whose every batch
        # overflows still records what it actually ran).
        if self._last_step_fallback:
            logged, flag = self._fallback_logged, "_fallback_logged"
        else:
            logged, flag = self._exchange_logged, "_exchange_logged"
        if not logged:
            setattr(self, flag, True)
            for k, pol in policy.items():
                obs.emit_event(
                    "exchange", table=k, policy=pol,
                    bytes_per_step=xbytes.get(k, 0),
                    fallback=self._last_step_fallback,
                )


# =========================================================================
# Device-resident tiered-store fast path (ISSUE 15)
# =========================================================================


# ``_pow2_pad`` (the shared kernel pad policy) is imported at the top.


class TieredDeviceEmbedding:
    """Hot-resident fast path binding an in-process
    :class:`~lightctr_tpu.embed.tiered.TieredEmbeddingStore`'s pinned
    device pair to the fused kernel chain (docs/TIERED_STORE.md
    "Device-resident hot tier").

    Per step: :meth:`gather` probes the store's SLOT TICKETS
    (``hot_slots`` + ``res_epoch``) for the batch's unique cover — when
    every id is hot-resident the forward rows are ONE
    ``ops.sparse_kernels.gather_rows`` off the pinned block (no store
    surface call, no host row traffic); any miss falls back to the
    authoritative ``pull_batch`` (creates, admission, promotion, SSP —
    the PR 8 contract path) and only the still-non-resident rows ride
    host memory.  :meth:`apply` then runs the batch's gradient rows
    through ONE fused ``merge_apply`` (segment merge + mean scale +
    health sumsq + adagrad apply) ALIASING the store's ``(rows,
    accums)`` pair in place — donated under jit on TPU, so the
    pull → dedup → gather → grad → merge → apply chain for hot-resident
    uids never leaves the device — and hands the pair back through
    ``adopt_device_tables`` (a reference swap pinned to the gather's
    ``res_epoch``: residency moved underneath means the tickets were
    stale and the adopt fails loud instead of writing through dead
    slots).  Non-resident ids push their merged gradients through
    ``push_batch`` (the store applies its exact in-place math wherever
    the row lives).

    Single-writer by contract: between a ``gather`` and its ``apply``
    nothing else may mutate the store (the fused apply aliases the live
    block — a concurrent residency change is unrecoverable, which is
    why the adopt is epoch-guarded).  The all-hot trajectory is
    bit-identical to the same JITTED ``merge_apply`` program over a
    dense table (the fast path's parity oracle, tested — jit is part
    of the oracle: XLA fusion contracts FMAs relative to eager
    op-by-op rounding); mixed batches update miss rows with
    the store's correctly-rounded eager math instead — each per-row
    step is the same adagrad recipe within documented kernel ulp.

    ``prefetch_next(ids)`` forwards the NEXT batch's unique cover to
    ``dispatch_prefetch`` so a miss-bearing pull commits off the staged
    plan instead of faulting synchronously.
    """

    def __init__(self, store, worker_id: int = 0, denom: float = 1.0,
                 registry=None):
        if not getattr(store, "device_hot", False):
            raise ValueError(
                "TieredDeviceEmbedding needs a device_hot store "
                "(TieredEmbeddingStore(device_hot=True))"
            )
        if store.updater != "adagrad":
            raise ValueError(
                "the fused merge_apply kernel is the sparse-adagrad "
                f"recipe; store updater {store.updater!r} unsupported"
            )
        self.store = store
        self.worker_id = int(worker_id)
        self.denom = float(denom)
        self.dim = int(store.dim)
        self.registry = registry if registry is not None else store.registry
        self.epoch = 0
        self.fast_steps = 0
        self.mixed_steps = 0
        self.stale_tickets = 0
        self._fused = {}
        # single-writer discipline vs the store's OWN prefetch worker:
        # a dispatch runs speculative admission (residency moves!) on a
        # background thread, so no dispatch may be in flight while a
        # slot ticket is outstanding — gather() drains the queue before
        # ticketing, and prefetch_next() defers its dispatch to the end
        # of the matching apply()
        self._ticket_open = False
        self._deferred_prefetch = None

    # -- forward: pull -> dedup -> gather -------------------------------------

    def gather(self, ids):
        """Batch ids (any shape, duplicates welcome) -> ``(rows_u
        [U, dim] jax, inv [M] jax int32, ticket)``: the deduped row
        cover on device plus the position map (``rows_u[inv]`` is the
        per-position view) and the slot ticket :meth:`apply` consumes."""
        ids_arr = np.ascontiguousarray(
            np.asarray(ids).reshape(-1), np.int64)
        uniq, inv = np.unique(ids_arr, return_inverse=True)
        store = self.store
        # no staging may run while the ticket is live (speculative
        # admission moves residency under the slot map — unrecoverable
        # once the fused apply has aliased the pair).  A wedged worker
        # must fail HERE, before any ticket exists, not after donation
        if not store.prefetch_wait(timeout=30.0):
            raise RuntimeError(
                "prefetch worker failed to drain within 30s — refusing "
                "to open slot tickets over an in-flight stage"
            )
        self._ticket_open = True
        slots = store.hot_slots(uniq)
        pulled = None
        if (slots < 0).any() or not len(uniq):
            pulled = store.pull_batch(uniq, self.epoch, self.worker_id)
            if pulled is None:
                raise RuntimeError(
                    "tiered pull withheld (SSP gate) — the adapter is "
                    "single-worker; advance the straggler first"
                )
            slots = store.hot_slots(uniq)
        epoch = store.res_epoch
        hot = slots >= 0
        u = len(uniq)
        up = _pow2_pad(max(u, 1))
        sp = np.zeros(up, np.int32)
        sp[:u][hot] = slots[hot]
        w, _ = store.device_tables()
        from lightctr_tpu.ops import sparse_kernels as _sk

        rows_u = _sk.gather_rows(w, jnp.asarray(sp))[:u]
        if not hot.all():
            midx = np.flatnonzero(~hot)
            rows_u = rows_u.at[jnp.asarray(midx)].set(
                jnp.asarray(pulled[midx], jnp.float32))
        ticket = {
            "uniq": uniq, "inv": inv, "slots": slots, "hot": hot,
            "res_epoch": epoch,
        }
        return rows_u, jnp.asarray(inv, jnp.int32), ticket

    # -- backward: grad -> merge -> apply -------------------------------------

    def _fused_fn(self, key):
        fn = self._fused.get(key)
        if fn is None:
            from lightctr_tpu.ops import sparse_kernels as _sk

            store = self.store
            lr, eps, denom = store.lr, store.eps, self.denom

            def f(w, a, uids, rows, seg):
                return _sk.merge_apply(
                    w, a, uids, rows, seg, lr=lr, eps=eps, denom=denom)

            donate = (0, 1) if jax.default_backend() == "tpu" else ()
            fn = jax.jit(f, donate_argnums=donate)
            # device-plane aliasing check (obs/device.py): a donated
            # table buffer that silently COPIED instead of aliasing
            # doubles HBM — no-op wrapper unless LIGHTCTR_DEVICE armed
            fn = obs_device.verify_donation(
                f"merge_apply_{key[0]}x{key[1]}", fn, donate_argnums=donate)
            self._fused[key] = fn
        return fn

    def apply(self, ticket, grad_rows):
        """Apply the step's per-position gradient rows ``[M, dim]``
        (aligned with the ``ids`` stream :meth:`gather` deduped; jax or
        numpy).  Hot-resident rows ride the fused aliased merge_apply;
        the rest push through the store surface.  Returns the merged
        hot rows' sum of squares (the health gradient-norm feed; 0.0
        when nothing was hot).  Bumps the adapter's SSP epoch — one
        gather/apply pair per step."""
        store = self.store
        uniq, inv, slots = ticket["uniq"], ticket["inv"], ticket["slots"]
        hot = ticket["hot"]
        u = len(uniq)
        telem = obs.enabled()
        reg = self.registry
        if store.res_epoch != ticket["res_epoch"]:
            # tickets went stale before any aliasing ran: the WHOLE batch
            # can still take the authoritative surface
            self.stale_tickets += 1
            if telem:
                reg.inc("trainer_tiered_stale_tickets_total")
            hot = np.zeros(u, bool)
        ssq = 0.0
        m = int(inv.shape[0])
        n_hot = int(hot.sum())
        if n_hot:
            hs = slots[hot].astype(np.int64)
            order = np.argsort(hs)
            sp = _pow2_pad(n_hot)
            uids_p = np.zeros(sp, np.int32)
            uids_p[:n_hot] = hs[order]
            # unique index -> merge segment (sorted-slot position); miss
            # and padding positions land in a pad segment whose rows are
            # zeroed below, so their merged sum is exactly zero
            seg_of = np.full(u, sp - 1, np.int32)
            seg_of[np.flatnonzero(hot)[order]] = np.arange(
                n_hot, dtype=np.int32)
            mp = _pow2_pad(m)
            g = jnp.asarray(grad_rows, jnp.float32)
            mask = jnp.asarray(hot[inv].astype(np.float32))[:, None]
            rows_p = jnp.zeros((mp, self.dim), jnp.float32)
            rows_p = rows_p.at[: m].set(g * mask)
            inv_p = np.full(mp, sp - 1, np.int32)
            inv_p[:m] = seg_of[inv]
            w, a = store.device_tables()
            fused = self._fused_fn((sp, mp))
            uids_j, inv_j = jnp.asarray(uids_p), jnp.asarray(inv_p)
            # register the fused program with the process catalog (specs
            # captured BEFORE the call — the tables are donated into it)
            obs_device.offer(f"merge_apply_{sp}x{mp}", fused,
                             (w, a, uids_j, rows_p, inv_j))
            w2, a2, ssq = fused(w, a, uids_j, rows_p, inv_j)
            store.adopt_device_tables(
                w2, a2, touched_slots=hs,
                expect_res_epoch=ticket["res_epoch"])
            if telem:
                reg.inc("trainer_tiered_fast_rows_total", n_hot)
        miss = ~hot
        if miss.any():
            g_np = np.asarray(grad_rows, np.float32).reshape(m, self.dim)
            gm = np.zeros((u, self.dim), np.float32)
            np.add.at(gm, inv, g_np)
            keys = uniq[miss]
            store.push_batch(self.worker_id, keys,
                             gm[miss] / self.denom, self.epoch)
            self.mixed_steps += 1
            if telem:
                reg.inc("trainer_tiered_pushed_rows_total", len(keys))
        else:
            self.fast_steps += 1
            if telem:
                reg.inc("trainer_tiered_fast_steps_total")
        self.epoch += 1
        self._ticket_open = False
        if self._deferred_prefetch is not None:
            nxt, self._deferred_prefetch = self._deferred_prefetch, None
            store.dispatch_prefetch(nxt)
        return ssq

    # -- overlapped fault prefetch -------------------------------------------

    def prefetch_next(self, ids) -> int:
        """Stage the NEXT batch's miss payloads behind this step
        (``dispatch_prefetch`` on the batch's unique cover — exactly the
        key stream the matching pull will carry).  Called between a
        gather and its apply, the dispatch is DEFERRED to the end of the
        apply: staging runs speculative admission, which must not move
        residency under an outstanding slot ticket."""
        ids_arr = np.unique(
            np.ascontiguousarray(np.asarray(ids).reshape(-1), np.int64))
        if self._ticket_open:
            self._deferred_prefetch = ids_arr
            return 0
        return self.store.dispatch_prefetch(ids_arr)
