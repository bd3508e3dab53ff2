"""Fused Pallas kernels for the sparse hot path — registry + dispatcher.

The per-step sparse tax every table pays — dedup-gather, segment-merge,
optimizer apply, payload quantize — lowers under plain XLA as SEPARATE HLOs
with full-size intermediates: the merged gradient rows are materialized,
then re-read by the optimizer; the quantile codec walks its payload once to
encode and once more for the EF residual.  The reference LightCTR earns its
throughput from a hand-tuned L0 SIMD layer (``common/avx.h``) doing each of
these in one pass; ∇SD (PAPERS.md, 2303.07030) makes the same case for
sparse formats as first-class compiled objects.  This module is that layer
for the TPU port:

  - :func:`dedup_ids` — unique+inverse over an id stream: the exact
    ``jnp.unique(..., size=K, fill_value=0)`` contract — sorted unique
    ids, full-rank inverse (ranks may exceed ``size`` when truncated,
    exactly like ``jnp.unique``), plus the distinct count.  Pallas
    variant is SORT-FREE: a blocked rank kernel (rank = #distinct values
    less than x, via first-occurrence flags); it does not lower at the
    trainer's width and is deselected on a TPU.  Its XLA twin — what
    every TPU run takes — is three sorts with payloads and one scan, and
    no K-sized gather or scatter (docs/KERNELS.md "The dedup").
  - :func:`merge_rows` — duplicate-id segment merge (``segment_sum``).
  - :func:`merge_apply` — one-pass segment-merge + scaled Adagrad apply
    over touched rows: gradient rows are read once and the merged rows are
    never materialized merged-then-applied (the fold of
    ``optim/fused_adagrad``'s row update into the merge).  Emits the
    merged sum-of-squares so the trainer's health gradient norm rides the
    same pass.  Its XLA twin — what a TPU runs at the trainer's widths —
    works on the live prefix of the dedup slots and not on all K of them
    (:func:`apply_ladder`, :func:`live_plan`; docs/KERNELS.md "The sized
    apply"), and on a mesh on each row shard's own run of them
    (:func:`shard_plan`, :func:`gather_shards`).
  - :func:`quantize_pack` / :func:`quantize_pack_ef` — quantile-codec
    payload packing (the wire codes of ``ops.quantize``) with the error-
    feedback residual folded into the same pass: compensate, encode,
    decode, fresh-error — one payload traversal.

Every kernel ships a pure-XLA **reference twin** (the code the call sites
ran before this module existed; ``merge_apply``'s has since been sized by
the live prefix, ``dedup_ids``' rewritten as sorts) and dispatch is
decided per kernel NAME — see :func:`resolve_impl`:

  - ``pallas``   — compiled Mosaic kernels; what ``auto`` picks on a TPU for
                   every kernel the registry does not deselect there.
  - ``interpret``— the same kernels under ``pallas_call(interpret=True)``
                   (CPU parity tests); forced by ``LIGHTCTR_KERNELS=interpret``.
  - ``xla``      — the reference twin; the default off-TPU, and ON a TPU the
                   implementation of every kernel registered with a
                   ``deselected`` reason (the Mosaic compiler's words on why
                   its Pallas form does not run at the width the trainer
                   uses; ROADMAP S2 decides repair or deletion).

``LIGHTCTR_KERNELS`` = ``auto`` (default) | ``pallas`` | ``interpret`` |
``xla``.  The pick is static: nothing falls from one implementation to
another because a lowering, a compile or a run failed — that is an error.
Every resolution is counted in ``trainer_kernel_path_total{phase,impl}``
(once per trace, not per step — the pick is static inside jit), so
``tools/metrics_report.py --kernels`` shows which implementation actually
ran, measured rather than assumed.

Modules register their kernels here (``optim/fused_adagrad``,
``nn/flash_attention`` self-register on import); the AST lint in
tests/test_obs.py pins every ``pallas_call`` site in the tree to a
registered kernel with a declared reference twin — a direct call with no
CPU-safe twin cannot land.
"""

from __future__ import annotations

import math
import os
from functools import lru_cache, partial
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lightctr_tpu import obs

ENV_FLAG = "LIGHTCTR_KERNELS"

#: the dispatch phases a kernel may declare (the ``phase`` label of
#: ``trainer_kernel_path_total``); metrics_report --kernels groups by these
KERNEL_PHASES = ("dedup", "merge", "apply", "pack", "gather", "adagrad",
                 "attention")


class KernelDef(NamedTuple):
    name: str
    phase: str            # one of KERNEL_PHASES
    reference: Callable   # the pure-XLA twin (the pre-kernel call-site code)
    pallas: Callable      # pallas impl; MUST accept interpret=bool kwarg
    #: set -> ``auto`` keeps the XLA twin on a TPU too; the string is the
    #: reason (the compiler's message at the trainer's width)
    deselected: Optional[str] = None


#: name -> KernelDef.  The single source of truth the lint walks.
KERNELS: Dict[str, KernelDef] = {}


def register_kernel(
    name: str, *, phase: str, reference: Callable, pallas: Callable,
    deselected: Optional[str] = None,
) -> None:
    """Register a fused kernel with its XLA reference twin.  Both are
    mandatory — off-TPU the reference IS the implementation, so a kernel
    without one could strand tier-1.  ``deselected`` takes the kernel out
    of what ``auto`` selects on a TPU, by name, with the reason."""
    if phase not in KERNEL_PHASES:
        raise ValueError(f"unknown kernel phase {phase!r}")
    if not callable(reference) or not callable(pallas):
        raise ValueError(f"kernel {name!r} needs callable reference AND pallas")
    KERNELS[name] = KernelDef(
        name=name, phase=phase, reference=reference, pallas=pallas,
        deselected=deselected,
    )


def resolve_impl(name: str) -> str:
    """Which implementation a dispatch call will run.

    ``LIGHTCTR_KERNELS=xla`` forces the reference; ``interpret`` forces the
    Pallas kernel under the interpreter (CPU parity testing); ``pallas``
    forces compiled Mosaic; ``auto`` (default) compiles Pallas on a TPU for
    every kernel not registered ``deselected`` and takes the reference
    everywhere else."""
    if name not in KERNELS:
        raise KeyError(f"unregistered kernel {name!r}")
    mode = os.environ.get(ENV_FLAG, "auto").strip().lower() or "auto"
    if mode in ("xla", "off", "reference", "0"):
        return "xla"
    if mode == "interpret":
        return "interpret"
    if mode == "pallas":
        return "pallas"
    if jax.default_backend() == "tpu" and KERNELS[name].deselected is None:
        return "pallas"
    return "xla"


def _record(phase: str, impl: str) -> None:
    obs.default_registry().inc(
        obs.labeled("trainer_kernel_path_total", phase=phase, impl=impl)
    )


def _resolve(name: str, impl: Optional[str] = None) -> Tuple[str, Callable]:
    """(impl, fn) for one dispatch: the telemetry counter records the pick
    that actually runs (callers pass ``impl`` when a static per-call rule
    already chose the twin)."""
    kd = KERNELS[name]
    impl = impl or resolve_impl(name)
    _record(kd.phase, impl)
    if impl == "xla":
        return impl, kd.reference
    return impl, partial(kd.pallas, interpret=(impl == "interpret"))


def next_pow2(n: int, floor: int = 8) -> int:
    """THE pad policy for kernel-facing dynamic lengths: the next power
    of two >= ``n`` (min ``floor``), so pallas grid counts and jit
    shapes land on a bounded ladder instead of compiling per batch
    size.  Train (sparse_trainer), serve (model/cache), and the tiered
    store's device paths all pad through this one helper."""
    out = floor
    while out < n:
        out *= 2
    return out


# =========================================================================
# (a) dedup: unique + inverse over an id stream
# =========================================================================


def _dedup_reference(ids: jax.Array, size: int):
    """Sorted unique padded with id 0, full-rank inverse and the distinct
    count, from three sorts and one scan: no K-sized gather, scatter or
    scatter-add (on a v5e each costs 5-9 sorts of the same K; PERF.md
    section 5).  Output for output what
    ``jnp.unique(ids, return_inverse=True, size=size, fill_value=0)``
    gives — the inverse is the rank among ALL distinct values even when
    ``size`` truncates the unique array, so the count is the last rank + 1.

    No sort is stable (a stable sort carries one more operand on a TPU,
    0.15 ms of 0.58 a stream at K = 159,744) and none needs to be: ties
    are either impossible or between elements the outputs cannot tell
    apart."""
    k = ids.shape[0]
    # the sorted ids come out of the sort as an operand, not by ids[perm];
    # equal ids get the same rank, so their order in perm moves nothing
    s, perm = jax.lax.sort((ids, jax.lax.iota(jnp.int32, k)), num_keys=1,
                           is_stable=False)
    first = jnp.concatenate([jnp.ones((1,), jnp.bool_), s[1:] != s[:-1]])
    rank = jnp.cumsum(first.astype(jnp.int32)) - 1
    count = rank[-1] + 1
    # ranks back in the order of the positions (zeros.at[perm].set(rank));
    # perm is a permutation: no ties
    _, inv = jax.lax.sort((perm, rank), num_keys=1, is_stable=False)
    # first occurrences ahead of the repeats, ascending: two keys and no
    # sentinel, so an id of any value and either width sorts right; only
    # repeats of one id tie
    _, packed = jax.lax.sort((~first, s), num_keys=2, is_stable=False)
    n = min(size, k)
    u = jnp.where(jax.lax.iota(jnp.int32, n) < count, packed[:n], 0)
    return jnp.pad(u, (0, size - n)), inv, count


def _dedup_kernel(ids_ref, inv_ref, uids_ref, count_ref, first_ref,
                  *, k, bk, nb, size):
    """Sort-free blocked rank dedup.  Phase 0 marks first occurrences
    (dup-count over earlier slots == 0), phase 1 ranks each id by the
    number of distinct smaller values (a masked [bk, bk]-tiled compare
    accumulation — O(K^2) compares on the VPU instead of a sort network)
    and scatters first-rank ids into the output slots; slot ``size`` is
    the dump slot for truncated/padded entries (sliced off outside)."""
    phase, b = pl.program_id(0), pl.program_id(1)
    start = b * bk
    x = ids_ref[pl.ds(start, bk), :]                       # [bk, 1]
    pos = start + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)

    @pl.when(phase == 0)
    def _firsts():
        def body(c, dup):
            y = ids_ref[pl.ds(c * bk, bk), :]              # [bk, 1]
            q = c * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, bk), 1)
            eq = (x == y.reshape(1, bk)) & (q < pos)
            return dup + jnp.sum(eq.astype(jnp.int32), axis=1, keepdims=True)

        # only blocks <= b can hold earlier slots
        dup = jax.lax.fori_loop(0, b + 1, body, jnp.zeros((bk, 1), jnp.int32))
        first_ref[pl.ds(start, bk), :] = (dup == 0).astype(jnp.int32)

    @pl.when(phase == 1)
    def _ranks():
        def body(c, rank):
            y = ids_ref[pl.ds(c * bk, bk), :]
            fy = first_ref[pl.ds(c * bk, bk), :]
            lt = (y.reshape(1, bk) < x) & (fy.reshape(1, bk) > 0)
            return rank + jnp.sum(lt.astype(jnp.int32), axis=1, keepdims=True)

        rank = jax.lax.fori_loop(0, nb, body, jnp.zeros((bk, 1), jnp.int32))
        inv_ref[pl.ds(start, bk), :] = rank

        @pl.when(b == 0)
        def _init():
            uids_ref[:, :] = jnp.zeros((size + 1, 1), jnp.int32)
            count_ref[0, 0] = 0

        valid = pos < k
        count_ref[0, 0] = jnp.maximum(
            count_ref[0, 0], jnp.max(jnp.where(valid, rank, -1)) + 1
        )

        def scatter(j, _):
            r = rank[j, 0]
            ok = (start + j < k) & (r < size)
            uids_ref[jnp.where(ok, r, size), 0] = x[j, 0]
            return 0

        jax.lax.fori_loop(0, bk, scatter, 0)


def _dedup_pallas(ids: jax.Array, size: int, *, interpret: bool):
    k = ids.shape[0]
    ids32 = ids.astype(jnp.int32)
    bk = min(256, max(8, 1 << (k - 1).bit_length()))
    kp = -(-k // bk) * bk
    if kp != k:
        # sentinel pads rank ABOVE every real id, so real ranks are
        # untouched and padded slots land in the dump slot
        ids32 = jnp.pad(ids32, (0, kp - k),
                        constant_values=np.iinfo(np.int32).max)
    nb = kp // bk
    inv, uids, count = pl.pallas_call(
        partial(_dedup_kernel, k=k, bk=bk, nb=nb, size=size),
        grid=(2, nb),
        out_shape=(
            jax.ShapeDtypeStruct((kp, 1), jnp.int32),      # inv (full ranks)
            jax.ShapeDtypeStruct((size + 1, 1), jnp.int32),  # uids + dump slot
            jax.ShapeDtypeStruct((1, 1), jnp.int32),       # distinct count
        ),
        scratch_shapes=[pltpu.VMEM((kp, 1), jnp.int32)],
        interpret=interpret,
    )(ids32.reshape(kp, 1))
    return (uids[:size, 0].astype(ids.dtype), inv[:k, 0], count[0, 0])


def dedup_ids(ids: jax.Array, size: Optional[int] = None):
    """Dispatch: unique+inverse over one id stream -> ``(uids, inv,
    count)``, the exact ``jnp.unique(ids, return_inverse=True, size=size,
    fill_value=0)`` contract plus the distinct count.  ``size`` defaults
    to ``len(ids)`` (no truncation); with ``size < count`` the unique
    array truncates while ``inv`` keeps full ranks — identical to
    ``jnp.unique`` (callers like the rs shard merge read the count to
    tally overflow).  On a TPU ``auto`` takes the XLA twin
    (:func:`_dedup_reference`: three sorts and a scan); the Pallas rank
    kernel is deselected there since PR 21."""
    ids = ids.reshape(-1)
    k = ids.shape[0]
    if size is None:
        size = k
    if k == 0:
        return (jnp.zeros((size,), ids.dtype), jnp.zeros((0,), jnp.int32),
                jnp.zeros((), jnp.int32))
    impl = None
    if jnp.dtype(ids.dtype).itemsize > 4 and resolve_impl("dedup_ids") != "xla":
        # the rank kernel compares in int32 — ids that may not fit (int64
        # streams in the billion-row-vocab regime) take the reference,
        # whose sorts are exact at any width
        impl = "xla"
    _, fn = _resolve("dedup_ids", impl=impl)
    return fn(ids, size)


# =========================================================================
# (b) segment merge + fused merge-apply
# =========================================================================


def _merge_reference(rows: jax.Array, inv: jax.Array, num_segments: int):
    return jax.ops.segment_sum(rows, inv, num_segments=num_segments)


def _merge_kernel(inv_ref, rows_ref, out_ref, *, m, bk, nseg):
    """Sequential scatter-accumulate: segment slot += row, in increasing
    slot order (the same accumulation order ``segment_sum`` applies, so
    the merge is bit-identical to the reference twin).  Out-of-range
    segments (truncated ranks) and padded slots add exact zeros to row 0,
    matching ``segment_sum``'s drop semantics."""
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _zero():
        out_ref[:, :] = jnp.zeros((nseg, out_ref.shape[1]), out_ref.dtype)

    def body(j, _):
        p = b * bk + j
        seg = inv_ref[p, 0]
        ok = (p < m) & (seg >= 0) & (seg < nseg)
        segc = jnp.where(ok, seg, 0)
        row = rows_ref[pl.ds(p, 1), :] * jnp.where(ok, 1.0, 0.0)
        out_ref[pl.ds(segc, 1), :] += row
        return 0

    jax.lax.fori_loop(0, bk, body, 0)


def _merge_pallas(rows: jax.Array, inv: jax.Array, num_segments: int,
                  *, interpret: bool):
    m = rows.shape[0]
    d = int(np.prod(rows.shape[1:])) if rows.ndim > 1 else 1
    flat = rows.reshape(m, d).astype(jnp.float32)
    bk = min(256, max(8, m))
    mp = -(-m // bk) * bk
    inv2 = jnp.pad(inv.astype(jnp.int32), (0, mp - m)).reshape(mp, 1)
    if mp != m:
        flat = jnp.pad(flat, ((0, mp - m), (0, 0)))
    out = pl.pallas_call(
        partial(_merge_kernel, m=m, bk=bk, nseg=num_segments),
        grid=(mp // bk,),
        out_shape=jax.ShapeDtypeStruct((num_segments, d), jnp.float32),
        interpret=interpret,
    )(inv2, flat)
    # the reference (segment_sum) preserves the payload dtype — match it
    return out.reshape((num_segments,) + rows.shape[1:]).astype(rows.dtype)


def merge_rows(rows: jax.Array, inv: jax.Array, num_segments: int):
    """Dispatch: duplicate-slot segment merge — ``segment_sum(rows, inv,
    num_segments)`` with the dedup convention's drop semantics for
    out-of-range segments."""
    if rows.shape[0] == 0:
        return jnp.zeros((num_segments,) + rows.shape[1:], rows.dtype)
    _, fn = _resolve("merge_rows")
    return fn(rows, inv, num_segments)


#: the live-prefix ladder (:func:`apply_ladder`): rungs in sixteenths of the
#: slot count K, closer together at the low end where a Criteo-shape batch
#: lands (28% of its slots distinct); at most 8 rungs, so a table's apply
#: compiles 2 x 9 small branches
_LADDER_SIXTEENTHS = (2, 3, 4, 5, 6, 8, 12, 16)
#: under this many slots the ladder is the one rung K: the tiered store's
#: few hundred slots and a test's few dozen gain nothing from a switch
LADDER_MIN_SLOTS = 8192


@lru_cache(maxsize=None)
def apply_ladder(k: int) -> Tuple[int, ...]:
    """The slot counts the sized apply may take at ``k`` dedup slots,
    ascending, the last one ``k``: a pure function of ``k``, so the host
    (``trainer_apply_slots_total``) and the device name the same rung."""
    if k < LADDER_MIN_SLOTS:
        return (k,)
    # multiples of 128: whole lane tiles of the index vector
    return tuple(sorted({min(k, -(-k * r // (16 * 128)) * 128)
                         for r in _LADDER_SIXTEENTHS}))


def ladder_slots(k: int, count: int) -> int:
    """Slots of the rung the apply takes for ``count`` live slots of ``k``:
    the smallest rung that holds them."""
    return next(s for s in apply_ladder(k) if s >= count)


def _slide(x: jax.Array, start, back: bool = False) -> jax.Array:
    """``x`` slid ``start`` slots along axis 0, zeros coming in: ``out[j]
    = x[start + j]``, or ``x[j - start]`` with ``back``.  A pad and a
    dynamic slice (``start`` may be traced, in ``[0, K]``)."""
    k = x.shape[0]
    wide = jnp.pad(x, ((k, 0) if back else (0, k),)
                   + ((0, 0),) * (x.ndim - 1))
    return jax.lax.dynamic_slice_in_dim(wide, k - start if back else start, k)


def shard_plan(uids: jax.Array, rows: int, lo=None):
    """``(idx, branch, start, count)`` for the table rows ``[lo, lo +
    rows)`` of one dedup-convention id vector ``uids`` [K]; ``lo=None`` is
    the whole table (``start`` is then the int 0 and nothing is slid).

    A slot is live when it is slot 0 or its id is not 0, and a shard's own
    when its id is also in the shard's range.  ``uids`` come sorted, so the
    own slots are one run ``[start, start + count)`` of the live prefix;
    the plan brings that run to slot 0: ``idx[j]`` is the LOCAL row
    ``uids[start + j] - lo`` for ``j < count`` and past the local table
    behind it (``rows + start + j``: ascending, so a scatter with
    ``mode="drop"`` skips it and a sorted, unique run stays sorted and
    unique over any rung).  The caller slides what it pairs with ``idx`` by
    the same ``start``.  ``branch`` indexes ``apply_ladder(K)`` by the
    smallest rung holding ``count`` — or is ``len(ladder)``, the undeclared
    full-K branch, when ``idx`` is not strictly ascending (the
    reduce-scatter exchange hands per-owner sorted segments with pads
    between them; own slots that are not one ascending run read so too).
    Then nothing is slid: ``start`` is 0 and ``idx`` names every own slot
    where it stands.  Sortedness is observed, never assumed."""
    k = uids.shape[0]
    # int64 id streams (the billion-row regime) keep their width
    slot = jnp.arange(k, dtype=jnp.promote_types(uids.dtype, jnp.int32))
    live = (uids != 0) | (slot == 0)
    ids = uids.astype(slot.dtype)
    if lo is None:
        start = 0
        idx = jnp.where(live, ids, rows + slot)
        count = jnp.max(jnp.where(live, slot + 1, 0))
        ascending = jnp.all(idx[1:] > idx[:-1])
    else:
        local = ids - jnp.asarray(lo, slot.dtype)
        own = live & (local >= 0) & (local < rows)
        start = jnp.sum(live & (local < 0), dtype=jnp.int32)
        count = jnp.sum(own, dtype=jnp.int32)
        idx = jnp.where(own, local, rows + slot)
        # the run at slot 0; the pads that come in behind slot K go on
        # ascending past the table.  Ascending with every own slot in it:
        # the run it was taken for
        run = jax.lax.dynamic_slice_in_dim(
            jnp.concatenate([idx, rows + k + slot]), start, k)
        ascending = (jnp.all(run[1:] > run[:-1])
                     & (jnp.sum(run < rows, dtype=jnp.int32) == count))
        idx = jnp.where(ascending, run, idx)
        start = jnp.where(ascending, start, 0)
    ladder = apply_ladder(k)
    rung = jnp.sum(count > jnp.asarray(ladder, jnp.int32))
    branch = jnp.where(ascending, rung, len(ladder)).astype(jnp.int32)
    return idx, branch, start, count


def live_plan(uids: jax.Array, vocab: int):
    """``(idx, branch)`` of :func:`shard_plan` for a whole table of
    ``vocab`` rows: ``idx`` is ``uids`` with every pad slot sent past the
    table (``vocab + slot``), ``branch`` the smallest rung of
    ``apply_ladder(K)`` that holds the live prefix (one pass over K
    int32), or the undeclared branch for ids seen out of order."""
    return shard_plan(uids, vocab)[:2]


def _ladder_branches(k: int, rung: Callable) -> list:
    """``rung(slots, ordered)`` for every rung of ``apply_ladder(k)``,
    then the undeclared full-K branch ``live_plan`` names for ids it saw
    out of order."""
    return [rung(s, True) for s in apply_ladder(k)] + [rung(k, False)]


def gather_live(block: jax.Array, idx: jax.Array, branch: jax.Array,
                zero_pads: bool = False):
    """``block[idx]`` over the rung ``branch`` names, zero rows behind it
    ([K, ...] whatever the rung).  ``block`` is used once in each branch,
    which is what lets XLA keep a donated table in place around the
    switch (docs/KERNELS.md, "Reading tools/aot_step.py").  A pad slot
    inside the rung reads the table's last row (clip) — no caller reads a
    pad slot's row — unless ``zero_pads`` asks for zeros there too."""
    k = idx.shape[0]
    mode = dict(mode="fill", fill_value=0) if zero_pads else dict(mode="clip")

    def rung(s, ordered):
        def f(block, idx):
            rows = jnp.take(block, idx[:s], axis=0,
                            indices_are_sorted=ordered, **mode)
            return jnp.pad(rows, ((0, k - s),) + ((0, 0),) * (rows.ndim - 1))
        return f

    return jax.lax.switch(branch, _ladder_branches(k, rung), block, idx)


def gather_shards(block: jax.Array, uids: jax.Array, axis_name: str):
    """``table[uids]`` in ``uids`` order ([K, ...], zero rows behind the
    live prefix) where ``block`` is this device's shard of the table's
    rows along the mapped axis ``axis_name``: call it inside a
    ``shard_map`` with ``uids`` replicated.  Each shard gathers its own
    run of the slots on its own rung (:func:`shard_plan`; that switch
    holds no collective) with zeros wherever a slot is not its own —
    the shards' rows are summed next, and clip's last row must not leak
    into the sum.  The join is a second switch, over the rung the WHOLE
    live prefix takes — its index comes from the replicated ``uids`` and
    is the same on every device: there the run is slid back to where it
    stands in ``uids`` and one ``psum`` adds the shards."""
    k, rows = uids.shape[0], block.shape[0]
    lo = jax.lax.axis_index(axis_name) * rows
    idx, branch, start, _ = shard_plan(uids, rows, lo)
    part = gather_live(block, idx, branch, zero_pads=True)
    tail = block.shape[1:]
    width = math.prod(tail)

    def rung(s, ordered):
        del ordered

        def f(part, start):
            # every run ends inside the whole prefix's rung, so the slide
            # and the sum are made at s, not at K — and flat, up to the
            # zeros behind the rung: XLA:TPU all-reduces an [s, 32]
            # operand row-major with its 32 lanes padded to 128, four
            # times the bytes, and moves a reshape that only wraps the
            # psum out of the way (docs/KERNELS.md, "On a mesh")
            own = _slide(part[:s].reshape(-1), start * width, back=True)
            joined = jax.lax.psum(own, axis_name)
            return jnp.pad(joined, (0, (k - s) * width)).reshape((k,) + tail)
        return f

    _, whole = live_plan(uids, rows * jax.lax.axis_size(axis_name))
    return jax.lax.switch(whole, _ladder_branches(k, rung), part, start)


def _scatter_live(table, accum, idx, delta, acc, branch):
    """``table[idx] += delta ; accum[idx] = acc`` over the rung ``branch``
    names.  Every live index is distinct and every pad is past the table
    and dropped, so each scatter says ``unique_indices``; the rungs also
    say ``indices_are_sorted`` (``live_plan`` saw it), which spares the
    sort XLA otherwise puts before a scatter."""
    k = idx.shape[0]

    def rung(s, ordered):
        def f(table, accum, idx, delta, acc):
            kw = dict(mode="drop", indices_are_sorted=ordered,
                      unique_indices=True)
            return (table.at[idx[:s]].add(delta[:s], **kw),
                    accum.at[idx[:s]].set(acc[:s], **kw))
        return f

    return jax.lax.switch(branch, _ladder_branches(k, rung),
                          table, accum, idx, delta, acc)


def _merge_apply_reference(
    table: jax.Array,
    accum: jax.Array,
    uids: jax.Array,
    rows: jax.Array,
    inv: Optional[jax.Array],
    lr: float,
    eps: float,
    denom: float,
    shard_axis: Optional[str] = None,
):
    """The XLA apply: segment-merge (when ``inv`` is given), scale, health
    sum-of-squares, then ``embed.table.sparse_adagrad_update``'s
    arithmetic — ``acc = a[u] + g^2 ; w[u] -= lr g rsqrt(acc + eps) ;
    a[u] = acc`` — over the live prefix of ``uids`` and not over all K
    slots: the accumulator rows come from one switch over the ladder
    (:func:`gather_live`), the arithmetic runs at K, one more switch
    scatters into table and accumulator (:func:`_scatter_live`).  The ids
    are trusted to be unique, as the contract states them, so nothing is
    deduplicated a second time.

    With ``shard_axis`` (see :func:`merge_apply`) ``table`` and ``accum``
    are one shard's rows: the plan is the shard's own
    (:func:`shard_plan`), the gradient rows are slid by its ``start`` to
    pair with it, and the same arithmetic runs on the shard's run of the
    slots over the shard's rung.  ``sumsq`` stays the whole payload's."""
    k = uids.shape[0]
    if inv is not None:
        merged = jax.ops.segment_sum(rows, inv, num_segments=k)
    else:
        merged = rows
    if denom != 1.0:
        merged = merged / denom
    sumsq = jnp.sum(merged * merged)
    g = merged.reshape((k,) + table.shape[1:]).astype(table.dtype)
    if shard_axis is None:
        idx, branch = live_plan(uids, table.shape[0])
    else:
        lo = jax.lax.axis_index(shard_axis) * table.shape[0]
        idx, branch, start, _ = shard_plan(uids, table.shape[0], lo)
        # rows behind the run are other shards' gradients: their slots
        # are past the table, and the scatters drop them
        g = _slide(g, start)
    acc = gather_live(accum, idx, branch) + g * g
    delta = -lr * g * jax.lax.rsqrt(acc + eps)
    new_table, new_accum = _scatter_live(table, accum, idx, delta, acc, branch)
    return new_table, new_accum, sumsq


#: rows per grid step of the row-DMA kernels (:func:`_apply_kernel`,
#: :func:`_gather_kernel`): 8 is the smallest row block the TPU tiling
#: rule admits for the gradient/output windows, and the number of row
#: copies each step keeps in flight
DMA_ROWS = 8


def _apply_kernel(uids_ref, g_ref, w_in, a_in, w_out, a_out, ssq_ref,
                  w_scr, a_scr, sems, *, lr, eps, denom, s, rb):
    """Fused scaled Adagrad apply over touched rows, ``rb`` rows per grid
    step.  Table and accumulator stay in HBM (``pl.ANY``) and alias their
    outputs, so the update is in place at any vocabulary; each live row
    is one async copy HBM -> its own VMEM slot, the fused update, and one
    copy back.  The step's copies are all started before the first wait:
    live rows are distinct (sorted unique uids), so nothing orders them.

    Pad slots — uid 0 beyond slot 0, the dedup convention, and the block
    round-up tail — carry zero gradient by contract and are skipped, so
    row 0 is touched at most once (by slot 0, which is either the real id
    0 or the smallest real id) and no row is ever written twice."""
    del w_in, a_in  # aliased into w_out / a_out
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _zero():
        ssq_ref[0, 0] = 0.0

    def each_live(fn):
        def body(j, carry):
            p = i * rb + j
            uid = uids_ref[p]

            @pl.when((p < s) & ((uid != 0) | (p == 0)))
            def _row():
                fn(j, uid)

            return carry

        jax.lax.fori_loop(0, rb, body, 0)

    def copies(j, uid, inbound):
        pairs = ((w_out.at[pl.ds(uid, 1), :], w_scr.at[j], sems.at[0, j]),
                 (a_out.at[pl.ds(uid, 1), :], a_scr.at[j], sems.at[1, j]))
        return [
            pltpu.make_async_copy(hbm, vmem, sem) if inbound
            else pltpu.make_async_copy(vmem, hbm, sem)
            for hbm, vmem, sem in pairs
        ]

    def update(j, uid):
        del uid
        g = g_ref[pl.ds(j, 1), :]
        if denom != 1.0:
            g = g / denom
        ssq_ref[0, 0] += jnp.sum(g * g)
        a_new = a_scr[j] + g * g
        a_scr[j] = a_new
        w_scr[j] = w_scr[j] - lr * g * jax.lax.rsqrt(a_new + eps)

    each_live(lambda j, uid: [c.start() for c in copies(j, uid, True)])
    each_live(lambda j, uid: [c.wait() for c in copies(j, uid, True)])
    each_live(update)
    each_live(lambda j, uid: [c.start() for c in copies(j, uid, False)])
    each_live(lambda j, uid: [c.wait() for c in copies(j, uid, False)])


def _merge_apply_pallas(
    table, accum, uids, rows, inv, lr, eps, denom, *, interpret: bool
):
    shape = table.shape
    vocab = shape[0]
    d = int(np.prod(shape[1:]))
    s = uids.shape[0]
    if inv is not None:
        # the segment merge is its own registered kernel with its own pick
        merged = merge_rows(rows.reshape(rows.shape[0], d), inv, s)
    else:
        merged = rows.reshape(s, d)
    rb = DMA_ROWS
    sp = -(-s // rb) * rb
    uids_p = jnp.pad(uids.astype(jnp.int32), (0, sp - s))
    merged_p = jnp.pad(merged.astype(jnp.float32), ((0, sp - s), (0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(sp // rb,),
        in_specs=[
            pl.BlockSpec((rb, d), lambda i, u: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        scratch_shapes=[
            # one (1, d) slab per in-flight row: the leading index is
            # untiled, so every copy lands on a whole buffer
            pltpu.VMEM((rb, 1, d), jnp.float32),
            pltpu.VMEM((rb, 1, d), jnp.float32),
            pltpu.SemaphoreType.DMA((2, rb)),
        ],
    )
    w2, a2, ssq = pl.pallas_call(
        partial(_apply_kernel, lr=lr, eps=eps, denom=denom, s=s, rb=rb),
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((vocab, d), table.dtype),
            jax.ShapeDtypeStruct((vocab, d), accum.dtype),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ),
        input_output_aliases={2: 0, 3: 1},
        interpret=interpret,
    )(uids_p, merged_p, table.reshape(vocab, d), accum.reshape(vocab, d))
    return w2.reshape(shape), a2.reshape(shape), ssq[0, 0]


def merge_apply(
    table: jax.Array,
    accum: jax.Array,
    uids: jax.Array,
    rows: jax.Array,
    inv: Optional[jax.Array] = None,
    *,
    lr: float,
    eps: float = 1e-7,
    denom: float = 1.0,
    shard_axis: Optional[str] = None,
):
    """Dispatch: one-pass segment-merge + scaled Adagrad apply over the
    touched rows of ``table``/``accum``.

    ``uids`` [S] follow the dedup convention (sorted unique, padding
    repeats id 0); ``rows`` is either the pre-merge [M, ...] gradient
    payload with its ``inv`` [M] segment map, or — ``inv=None`` — already
    per-uid rows [S, ...] (the reduce-scatter path, whose merge happened
    owner-side mid-exchange).  ``denom`` scales the merged rows
    (``merged / denom`` — the exchange's mean) before the apply.

    ``shard_axis`` names the mapped mesh axis the table's rows are sharded
    over, for a call inside a ``shard_map``: ``table`` / ``accum`` are
    then this device's rows ``[i * V_e, (i + 1) * V_e)``, ``uids`` and
    ``rows`` the replicated global ones, and each shard applies the rows
    it owns on the rung that holds them (docs/KERNELS.md, "On a mesh").
    A static rule takes the XLA twin there: the Pallas kernel reads whole-
    table ids.

    Returns ``(table', accum', sumsq)``; ``sumsq`` is the merged rows'
    sum of squares (the health gradient-norm contribution) computed in
    the same pass.  The trajectory matches the reference chain
    ``segment_sum -> /denom -> sparse_adagrad_update`` to the last
    FMA-contraction ulp; ``sumsq`` may differ in final-ulp accumulation
    order.

    Padded id-0 slots are ZERO-GRADIENT BY CONTRACT, and for ``inv=None``
    payloads this dispatch enforces it before either impl runs: the coded
    reduce-scatter exchange leaves decoded dump-slot noise (half-bucket
    midpoints) in foreign shards' id-0 slots, and without the mask the
    reference would train real row 0 on that noise while the fused kernel
    (which skips pad slots) drops it — the enforced zero keeps every impl
    on the identical trajectory and keeps codec noise off row 0.  Merged
    ``inv`` payloads need no mask: pad segments are never referenced,
    their sums are exactly zero."""
    if inv is None:
        k = uids.shape[0]
        valid = ~((uids == 0) & (jnp.arange(k) > 0))
        rows = rows * valid.astype(rows.dtype).reshape(
            (-1,) + (1,) * (rows.ndim - 1)
        )
    if shard_axis is not None:
        _, fn = _resolve("merge_apply", "xla")
        return fn(table, accum, uids, rows, inv, lr, eps, denom, shard_axis)
    _, fn = _resolve("merge_apply")
    return fn(table, accum, uids, rows, inv, lr, eps, denom)


# =========================================================================
# (b2) row gather: the device-resident row path's read half
# =========================================================================
#
# ``rows = block[idx]`` — the gather every consumer of a device-resident
# row block runs: the tiered store's hot-tier pulls, the trainer's
# hot-resident fast path, and the serving cache's device-block hits
# (ISSUE 15: train and serve share ONE row path through this entry).
# The Pallas twin is the read half of the merge_apply row-DMA pattern:
# the block stays in HBM, the scalar-prefetched indices steer one async
# row copy each, so a row moves HBM -> VMEM -> HBM once with no
# [n, vocab] one-hot or host round trip.  Indices MUST be in range
# (both impls clip rather than trap — jnp.take(mode="clip"), pinned
# explicitly because take's default mode fills NaN).


def _gather_reference(block: jax.Array, idx: jax.Array):
    # mode="clip" explicitly: jnp.take's DEFAULT out-of-range mode is
    # "fill" (NaN rows), which would silently diverge from the pallas
    # twin's clipped window
    return jnp.take(block, idx, axis=0, mode="clip")


def _gather_kernel(idx_ref, src, out_ref, scr, sems, *, rb):
    """``rb`` row copies in flight per grid step: HBM row -> its own VMEM
    slot, then one vector store into the step's ``(rb, d)`` output
    window (the index array is padded to the block, so every slot is
    live)."""
    i = pl.program_id(0)

    def copy(j):
        return pltpu.make_async_copy(
            src.at[pl.ds(idx_ref[i * rb + j], 1), :], scr.at[j], sems.at[j]
        )

    def start(j, carry):
        copy(j).start()
        return carry

    def land(j, carry):
        copy(j).wait()
        out_ref[pl.ds(j, 1), :] = scr[j]
        return carry

    jax.lax.fori_loop(0, rb, start, 0)
    jax.lax.fori_loop(0, rb, land, 0)


def _gather_pallas(block: jax.Array, idx: jax.Array, *, interpret: bool):
    n = idx.shape[0]
    shape = block.shape
    d = int(np.prod(shape[1:]))
    src = block.reshape(shape[0], d)
    rb = DMA_ROWS
    np_ = -(-n // rb) * rb
    # clip like jnp.take: every copy window must stay in range
    idx32 = jnp.pad(jnp.clip(idx.astype(jnp.int32), 0, shape[0] - 1),
                    (0, np_ - n))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(np_ // rb,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((rb, d), lambda i, u: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((rb, 1, d), block.dtype),
            pltpu.SemaphoreType.DMA((rb,)),
        ],
    )
    out = pl.pallas_call(
        partial(_gather_kernel, rb=rb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((np_, d), block.dtype),
        interpret=interpret,
    )(idx32, src)
    return out[:n].reshape((n,) + shape[1:])


def gather_rows(block: jax.Array, idx: jax.Array):
    """Dispatch: ``block[idx]`` row gather — ``jnp.take(block, idx,
    axis=0)`` semantics (out-of-range clips).  The read half of the
    device-resident row path: hot-tier pulls, the trainer fast path's
    table assembly, and serving-cache device hits all route here, so
    train and serve share one gather kernel."""
    idx = idx.reshape(-1)
    if idx.shape[0] == 0:
        return jnp.zeros((0,) + block.shape[1:], block.dtype)
    _, fn = _resolve("gather_rows")
    return fn(block, idx)


# =========================================================================
# (c) quantize-on-the-fly payload packing (+ folded EF residual)
# =========================================================================


def _qp_reference(table, x: jax.Array):
    from lightctr_tpu.ops import quantize

    return quantize.compress(table, x)


def _qp_kernel(bnd_ref, x_ref, codes_ref, *, nbp, bc, code_bits):
    """Compare-count encode: ``searchsorted(boundaries, x, side='left')``
    == the number of boundaries strictly below x — a chunked broadcast
    compare-accumulate, bit-identical to the codec's binary search."""
    x = x_ref[...]                                         # [bp, 1]

    def body(c, acc):
        bb = bnd_ref[0, pl.ds(c * bc, bc)]                 # [bc]
        return acc + jnp.sum((x > bb).astype(jnp.int32), axis=1,
                             keepdims=True)

    acc = jax.lax.fori_loop(0, nbp // bc, body,
                            jnp.zeros(x.shape, jnp.int32))
    codes_ref[...] = acc.astype(codes_ref.dtype)


def _qp_flatten(table, x):
    """(boundaries [1, NBp] +inf-padded, flat [P, 1], chunk, code dtype)."""
    nb = int(table.boundaries.shape[0])
    bc = min(256, max(8, nb))
    nbp = -(-nb // bc) * bc
    bnd = table.boundaries.astype(jnp.float32)
    if nbp != nb:
        bnd = jnp.pad(bnd, (0, nbp - nb), constant_values=jnp.inf)
    dtype = jnp.uint8 if table.bits <= 8 else jnp.uint16
    flat = x.reshape(-1, 1).astype(jnp.float32)
    return bnd.reshape(1, nbp), flat, bc, nbp, dtype


def _qp_pallas(table, x: jax.Array, *, interpret: bool):
    bnd, flat, bc, nbp, dtype = _qp_flatten(table, x)
    p = flat.shape[0]
    bp = min(1024, max(8, p))
    pp = -(-p // bp) * bp
    if pp != p:
        flat = jnp.pad(flat, ((0, pp - p), (0, 0)))
    kernel = partial(_qp_kernel, nbp=nbp, bc=bc, code_bits=table.bits)
    codes = pl.pallas_call(
        kernel,
        grid=(pp // bp,),
        out_shape=jax.ShapeDtypeStruct((pp, 1), dtype),
        in_specs=[
            pl.BlockSpec((1, nbp), lambda i: (0, 0)),
            pl.BlockSpec((bp, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bp, 1), lambda i: (i, 0)),
        interpret=interpret,
    )(bnd, flat)
    return codes[:p, 0].reshape(x.shape)


def _wide_codes_impl(table) -> Optional[str]:
    """Codes wider than 8 bits keep the XLA twin's binary search on every
    backend: the compare-count sweep the pack kernels run would pay 2^bits
    compares per element (and a 2^bits one-hot decode)."""
    return "xla" if table.bits > 8 else None


def quantize_pack(table, x: jax.Array) -> jax.Array:
    """Dispatch: float payload -> quantile codes, bit-identical to
    ``ops.quantize.compress`` (the wire pack every coded collective hop
    ships).  Codes up to 8 bits — the 4-bit sub-byte tables included —
    ride the compare-count sweep; wider tables take the reference
    (:func:`_wide_codes_impl`)."""
    _, fn = _resolve("quantize_pack", impl=_wide_codes_impl(table))
    return fn(table, x)


def quantize_pack_packed(table, x: jax.Array) -> jax.Array:
    """:func:`quantize_pack` plus the sub-byte WIRE form: 4-bit-and-under
    tables bit-pack two codes per byte (``ops.quantize.pack_nibbles`` —
    the ``wire_bits=4`` codec `dist.collectives._wire_row_bytes` prices);
    wider tables return their codes unchanged.  Receiver side:
    ``unpack_nibbles(packed, x.size)`` then ``quantize.extract`` —
    bit-parity with the unpacked reference codec is the contract
    (tests/test_sparse_kernels.py)."""
    codes = quantize_pack(table, x)
    if table.bits <= 4:
        from lightctr_tpu.ops.quantize import pack_nibbles

        return pack_nibbles(codes)
    return codes


def _qp_ef_reference(table, rows, carried, mask):
    """The `_ag_merge_rows` EF encode sequence: compensate with last
    step's carry, encode, decode, fresh error — exactly the chain the
    fused kernel runs in one pass."""
    from lightctr_tpu.ops import quantize

    val = rows + carried * mask
    codes = quantize.compress(table, val)
    dec = quantize.extract(table, codes)
    return codes, (val - dec - carried) * mask


def _qp_ef_kernel(bnd_ref, val_ref, rows_ref, car_ref, mask_ref,
                  codes_ref, delta_ref, *, nbp, bc, nvp, vc):
    """One pass over the payload: val = rows + carried*mask; encode
    (compare-count); decode (chunked one-hot masked sum — exact: every
    non-selected term contributes a signed zero); fresh EF error."""
    rows = rows_ref[...]
    car = car_ref[...]
    m = mask_ref[...]
    val = rows + car * m

    def cbody(c, acc):
        bb = bnd_ref[0, pl.ds(c * bc, bc)]
        return acc + jnp.sum((val > bb).astype(jnp.int32), axis=1,
                             keepdims=True)

    codes = jax.lax.fori_loop(0, nbp // bc, cbody,
                              jnp.zeros(val.shape, jnp.int32))

    def dbody(c, dec):
        vv = val_ref[0, pl.ds(c * vc, vc)]                 # [vc]
        idx = c * vc + jax.lax.broadcasted_iota(
            jnp.int32, (codes.shape[0], vc), 1
        )
        sel = (codes == idx).astype(jnp.float32)
        return dec + jnp.sum(vv * sel, axis=1, keepdims=True)

    dec = jax.lax.fori_loop(0, nvp // vc, dbody,
                            jnp.zeros(val.shape, jnp.float32))
    codes_ref[...] = codes.astype(codes_ref.dtype)
    delta_ref[...] = (val - dec - car) * m


def _qp_ef_pallas(table, rows, carried, mask, *, interpret: bool):
    bnd, flat, bc, nbp, dtype = _qp_flatten(table, rows)
    nv = int(table.values.shape[0])
    vc = min(256, max(8, nv))
    nvp = -(-nv // vc) * vc
    vals = table.values.astype(jnp.float32)
    if nvp != nv:
        vals = jnp.pad(vals, (0, nvp - nv))
    car = carried.reshape(-1, 1).astype(jnp.float32)
    msk = jnp.broadcast_to(mask, rows.shape).reshape(-1, 1).astype(
        jnp.float32
    )
    p = flat.shape[0]
    bp = min(1024, max(8, p))
    pp = -(-p // bp) * bp
    if pp != p:
        flat = jnp.pad(flat, ((0, pp - p), (0, 0)))
        car = jnp.pad(car, ((0, pp - p), (0, 0)))
        msk = jnp.pad(msk, ((0, pp - p), (0, 0)))
    codes, delta = pl.pallas_call(
        partial(_qp_ef_kernel, nbp=nbp, bc=bc, nvp=nvp, vc=vc),
        grid=(pp // bp,),
        out_shape=(
            jax.ShapeDtypeStruct((pp, 1), dtype),
            jax.ShapeDtypeStruct((pp, 1), jnp.float32),
        ),
        in_specs=[
            pl.BlockSpec((1, nbp), lambda i: (0, 0)),
            pl.BlockSpec((1, nvp), lambda i: (0, 0)),
            pl.BlockSpec((bp, 1), lambda i: (i, 0)),
            pl.BlockSpec((bp, 1), lambda i: (i, 0)),
            pl.BlockSpec((bp, 1), lambda i: (i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((bp, 1), lambda i: (i, 0)),
            pl.BlockSpec((bp, 1), lambda i: (i, 0)),
        ),
        interpret=interpret,
    )(bnd, vals.reshape(1, nvp), flat, car, msk)
    return (codes[:p, 0].reshape(rows.shape),
            delta[:p, 0].reshape(rows.shape))


def quantize_pack_ef(table, rows: jax.Array, carried: jax.Array,
                     mask: jax.Array):
    """Dispatch: EF-folded payload pack -> ``(codes, delta)`` where
    ``val = rows + carried*mask``, ``codes = compress(val)`` and
    ``delta = (val - extract(codes) - carried) * mask`` — the fresh
    error-feedback contribution the caller scatters back at the rows'
    table slots.  One traversal instead of the reference's
    compensate/encode/decode/error chain.  8-bit-and-under codes take
    the Pallas path (see :func:`quantize_pack`)."""
    _, fn = _resolve("quantize_pack_ef", impl=_wide_codes_impl(table))
    return fn(table, rows, carried, mask)


def _qp_ef_update_reference(table, rows, uids, residual, mask):
    """The caller-side EF sequence the folded kernel replaces: gather the
    carry, compensate, encode, decode, scatter the fresh error back at
    the rows' slots — the ``residual.at[uids].add(delta)`` pass every EF
    call site used to run separately.  The decoded view rides along so
    callers needing it (the rs overflow-drop correction) pay no second
    ``extract`` pass."""
    from lightctr_tpu.ops import quantize

    carried = jnp.take(residual, uids, axis=0)
    val = rows + carried * mask
    codes = quantize.compress(table, val)
    dec = quantize.extract(table, codes)
    new_residual = residual.at[uids].add((val - dec - carried) * mask)
    return codes, new_residual, dec


def _qp_ef_update_kernel(uids_ref, bnd_ref, vals_ref, rows_ref, mask_ref,
                         res_ref, codes_ref, res_out, dec_ref, *, s, nbp,
                         bc, nvp, vc):
    """Folded EF pack: per grid step one payload row — the scalar-
    prefetched uid steers the (1, dim) residual window (the merge_apply
    gather pattern), so compensate / encode (compare-count) / decode
    (chunked one-hot) / fresh-error / CARRY WRITE-BACK are one pass and
    the residual scatter never runs as a separate HLO.  Padded slots
    (mask 0) write their carry window back unchanged — an identity
    revisit, safe under either aliasing semantics; the caller rotates
    original slot 0 to run last, so the one real write of a
    multiply-visited row lands after its pad revisits."""
    r = rows_ref[...]                                      # [1, d]
    m = mask_ref[...]                                      # [1, 1]
    car = res_ref[...]                                     # [1, d]
    val = r + car * m

    def cbody(c, acc):
        bb = bnd_ref[0, pl.ds(c * bc, bc)]                 # [bc]
        return acc + jnp.sum(
            (val.reshape(-1, 1) > bb).astype(jnp.int32), axis=1,
        ).reshape(val.shape)

    codes = jax.lax.fori_loop(0, nbp // bc, cbody,
                              jnp.zeros(val.shape, jnp.int32))

    def dbody(c, dec):
        vv = vals_ref[0, pl.ds(c * vc, vc)]                # [vc]
        idx = c * vc + jax.lax.broadcasted_iota(
            jnp.int32, (val.shape[1], vc), 1
        )
        sel = (codes.reshape(-1, 1) == idx).astype(jnp.float32)
        return dec + jnp.sum(vv * sel, axis=1).reshape(val.shape)

    dec = jax.lax.fori_loop(0, nvp // vc, dbody,
                            jnp.zeros(val.shape, jnp.float32))
    codes_ref[...] = codes.astype(codes_ref.dtype)
    res_out[...] = car + (val - dec - car) * m
    dec_ref[...] = dec
    del s


def _qp_ef_update_pallas(table, rows, uids, residual, mask,
                         *, interpret: bool):
    s = rows.shape[0]
    d = int(np.prod(rows.shape[1:])) if rows.ndim > 1 else 1
    vocab = residual.shape[0]
    flat = rows.reshape(s, d).astype(jnp.float32)
    res2 = residual.reshape(vocab, d).astype(jnp.float32)
    msk = jnp.broadcast_to(
        jnp.asarray(mask, jnp.float32).reshape(s, -1)[:, :1], (s, 1)
    )
    nb = int(table.boundaries.shape[0])
    bc = min(256, max(8, nb))
    nbp = -(-nb // bc) * bc
    bnd = table.boundaries.astype(jnp.float32)
    if nbp != nb:
        bnd = jnp.pad(bnd, (0, nbp - nb), constant_values=jnp.inf)
    nv = int(table.values.shape[0])
    vc = min(256, max(8, nv))
    nvp = -(-nv // vc) * vc
    vals = table.values.astype(jnp.float32)
    if nvp != nv:
        vals = jnp.pad(vals, (0, nvp - nv))
    # rotate original slot 0 to run LAST: pad revisits of a shared uid-0
    # window must precede the one real write
    uids_r = jnp.roll(uids.astype(jnp.int32), -1)
    flat_r = jnp.roll(flat, -1, axis=0)
    msk_r = jnp.roll(msk, -1, axis=0)
    dtype = jnp.uint8 if table.bits <= 8 else jnp.uint16
    spec_seq = pl.BlockSpec((1, d), lambda i, u: (i, 0))
    spec_seq1 = pl.BlockSpec((1, 1), lambda i, u: (i, 0))
    spec_bnd = pl.BlockSpec((1, nbp), lambda i, u: (0, 0))
    spec_val = pl.BlockSpec((1, nvp), lambda i, u: (0, 0))
    spec_row = pl.BlockSpec((1, d), lambda i, u: (u[i], 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(s,),
        in_specs=[spec_bnd, spec_val, spec_seq, spec_seq1, spec_row],
        out_specs=[spec_seq, spec_row, spec_seq],
    )
    codes_r, new_res, dec_r = pl.pallas_call(
        partial(_qp_ef_update_kernel, s=s, nbp=nbp, bc=bc, nvp=nvp, vc=vc),
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((s, d), dtype),
            jax.ShapeDtypeStruct((vocab, d), jnp.float32),
            jax.ShapeDtypeStruct((s, d), jnp.float32),
        ),
        input_output_aliases={5: 1},
        interpret=interpret,
    )(uids_r, bnd.reshape(1, nbp), vals.reshape(1, nvp), flat_r, msk_r,
      res2)
    codes = jnp.roll(codes_r, 1, axis=0)
    dec = jnp.roll(dec_r, 1, axis=0)
    return (codes.reshape(rows.shape),
            new_res.reshape(residual.shape).astype(residual.dtype),
            dec.reshape(rows.shape))


def quantize_pack_ef_update(table, rows: jax.Array, uids: jax.Array,
                            residual: jax.Array, mask: jax.Array):
    """Dispatch: EF pack with the residual scatter FOLDED IN ->
    ``(codes, new_residual, dec)`` — ``dec`` is the receiver-side
    decoded view, computed inside the pass anyway and returned so
    callers that need it (the rs overflow-drop correction) pay no
    second ``extract``.  ``rows`` [S, ...] follow the dedup
    convention with ``uids`` [S] naming their table slots; ``residual``
    is the [vocab, ...] table-keyed carry and ``mask`` the validity mask
    over slots (pads must neither read nor write the carry).  One pass
    computes ``val = rows + residual[uids]*mask``, the codes, the decode
    and writes ``residual[uids] += (val - dec - carried) * mask`` in
    place — the carry update that every call site used to run as a
    separate gather + scatter (the PR 9 follow-up).  ``uids``/``mask``
    MUST honor the dedup convention — at most one UNMASKED slot per uid
    (the pallas impl writes windows where the reference accumulates, so
    duplicate unmasked slots would diverge).  8-bit-and-under codes take
    the Pallas path; wider tables resolve to the reference (the chunked
    one-hot decode over 2^16 values is not worth VPU time)."""
    if rows.shape[0] == 0:
        dtype = jnp.uint8 if table.bits <= 8 else jnp.uint16
        return (jnp.zeros(rows.shape, dtype), residual,
                jnp.zeros(rows.shape, jnp.float32))
    _, fn = _resolve("quantize_pack_ef_update",
                     impl=_wide_codes_impl(table))
    return fn(table, rows, uids, residual, mask)


# Deselection reasons are the TPU v5e's own words at the Criteo-shape
# Wide&Deep width (K = 4096 x 39 = 159,744 ids, vocab 2^20, dim 32; chip
# run of PR 21, CHANGES.md).  ROADMAP S2 decides repair or deletion.
register_kernel(
    "dedup_ids", phase="dedup",
    reference=_dedup_reference, pallas=_dedup_pallas,
    deselected="Pallas lowering: 'Cannot store scalars to VMEM' (the "
               "per-id uids/count stores); also whole (K, 1) id arrays in "
               "VMEM and O(K^2) compares",
)
_ROW_DMA_REASON = (
    "Mosaic: 'Slice shape along dimension 1 must be aligned to tiling "
    "(128), but is 32' — a row copy out of a (vocab, 32) HBM operand "
    "(memref<1048576x128xf32, tiled<(1,128)>>) cannot address 32 lanes"
)
register_kernel("gather_rows", phase="gather",
                reference=_gather_reference, pallas=_gather_pallas,
                deselected=_ROW_DMA_REASON)
register_kernel(
    "merge_rows", phase="merge",
    reference=_merge_reference, pallas=_merge_pallas,
    deselected="XLA:TPU compile: 'Ran out of memory in memory space vmem. "
               "Used 156.00M of 128.00M' at M = 159,744 (whole (M, 1) "
               "segment map and [M, d] payload as VMEM windows, no "
               "BlockSpec); compiles and matches at M = 2048",
)
register_kernel("merge_apply", phase="apply",
                reference=_merge_apply_reference, pallas=_merge_apply_pallas,
                deselected=_ROW_DMA_REASON)
register_kernel("quantize_pack", phase="pack",
                reference=_qp_reference, pallas=_qp_pallas)
register_kernel("quantize_pack_ef", phase="pack",
                reference=_qp_ef_reference, pallas=_qp_ef_pallas)
register_kernel(
    "quantize_pack_ef_update", phase="pack",
    reference=_qp_ef_update_reference, pallas=_qp_ef_update_pallas,
    deselected="Pallas lowering: the (1, d) row windows break 'the last "
               "two dimensions of your block shape are divisible by 8 and "
               "128 respectively, or be equal to the respective dimensions "
               "of the overall array'",
)
