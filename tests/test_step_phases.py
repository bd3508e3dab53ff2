"""The jitted step names every phase it runs: compiled on the CPU at test
size, each instruction of the step of each benchmark cell's model that
carries a name stack lies under one of the five phase scopes or is the
differentiated loss's, the ``model`` phase (the rule that reads a device
trace is ``benchmarks/harness/phases.phase_of``), and the
host's ``trainer/input`` span says what crossed its boundary — when it is
recorded, and at no cost when it is not."""

import collections
import functools
import re

import jax
import numpy as np
import pytest

from benchmarks.harness import phases
from lightctr_tpu import TrainConfig, obs
from lightctr_tpu.core.mesh import MeshSpec, make_mesh
from lightctr_tpu.data import ingest
from lightctr_tpu.models import fm, kimi_linear, widedeep
from lightctr_tpu.models.sparse_trainer import SparseTableCTRTrainer
from lightctr_tpu.obs import trace

from test_sharded_trainer import _B, _F, _PD, _PV, _row_sharded, _wd_batch

_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = .*? ([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SHARD_MAP_LITERAL = re.compile(r"jit\(step\)/shard_map(/broadcast\.\d+)?")


def _widedeep(mesh):
    kw = dict(mesh=mesh, param_shardings=_row_sharded(mesh)) if mesh else {}
    tr = SparseTableCTRTrainer(
        widedeep.init(jax.random.PRNGKey(4), _PV, _F, _PD), widedeep.logits,
        TrainConfig(learning_rate=0.1),
        sparse_tables={"w": ["fids"], "embed": ["rep_fids"]}, **kw)
    return tr, _wd_batch(np.arange(1, 4000), 0)


def _fm(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    kw = {}
    if mesh:
        kw = dict(mesh=mesh, param_shardings={
            "w": NamedSharding(mesh, P("embed")),
            "v": NamedSharding(mesh, P("embed", None))})
    tr = SparseTableCTRTrainer(
        fm.init(jax.random.PRNGKey(4), _PV, 8), fm.logits,
        TrainConfig(learning_rate=0.1, lambda_l2=0.001),
        fused_fn=fm.logits_with_l2,
        sparse_tables={"w": ["fids"], "v": ["fids"]}, **kw)
    return tr, _wd_batch(np.arange(1, 4000), 0)


def _kimi(mesh):
    assert mesh is None
    params, logits = kimi_linear.build(jax.random.PRNGKey(0))
    tr = SparseTableCTRTrainer(
        params, logits,
        TrainConfig(learning_rate=0.05, lambda_l2=0.0, loss="softmax_xent"),
        sparse_tables={"embed": ["tokens"]})
    docs = [list(range(1, 20)), list(range(5, 40)), list(range(30, 50))]
    return tr, ingest.sequence_batch(ingest.pack_documents(docs, 32))


def _instructions(tr, batch):
    """``[(opcode, name stack)]`` of the compiled step's named instructions
    (XLA makes a called computation's names whole as it inlines it)."""
    return _named(jax.jit(tr._build_step(), donate_argnums=(0, 1)).lower(
        tr._params, tr._opt_state, tr._put(batch)).compile().as_text())


def _named(text):
    out = []
    for line in text.splitlines():
        op, name = _INSTRUCTION.match(line), _OP_NAME.search(line)
        if op and name and name.group(1).startswith("jit(step)/"):
            out.append((op.group(1), name.group(1)))
    return out


X4 = dict(data=2, embed=2)


@functools.lru_cache(maxsize=None)
def _built(build, mesh):
    """``(trainer, batch, _instructions of its step)``, compiled once."""
    tr, batch = build(make_mesh(MeshSpec(**X4)) if mesh else None)
    return tr, batch, _instructions(tr, batch)


@pytest.mark.parametrize("build, axes", [
    (_widedeep, None), (_widedeep, X4), (_fm, None), (_fm, X4), (_kimi, None),
], ids=["widedeep-one_device", "widedeep-data2xembed2", "fm-one_device",
        "fm-data2xembed2", "kimi_linear-one_device"])
def test_every_named_instruction_of_the_step_lies_under_one_phase(build, axes):
    _, _, instructions = _built(build, bool(axes))
    found = collections.defaultdict(set)
    unscoped = []
    names = set()
    for op, name in instructions:
        names.add(name)
        phase = phases.phase_of(name)
        # the model's pass opens no scope of its own: what falls to it is
        # what the step differentiates, and nothing the step left unnamed
        # (but the literals of the mesh step's per-replica shard_map, which
        # the partitioner names after it: constants and their broadcasts)
        if phase is None or (phase == "model" and "jvp(" not in name
                             and not (op in ("constant", "broadcast")
                                      and _SHARD_MAP_LITERAL.fullmatch(name))):
            unscoped.append((op, name))
        found[op].add(phase)
    assert not unscoped, unscoped[:20]
    # ... so a stack inside it (``seq/kda/scan``, which a reader matches in a
    # label cut at 120 characters) is no longer for the phases
    assert not [n for n in names if "model" in n.replace("model/expand", "")]
    # all six phases are there
    assert set().union(*found.values()) == set(phases.PHASES)
    # the sparse phases' sorts are the dedup's (the sequence tower's router
    # sorts too, inside the model); the model's takes and their transposes'
    # scatter-adds are the expansion's, the other gathers and scatters the
    # forward gather's and the apply's; the products are the model's
    assert found["sort"] - {"model"} == {"dedup"}
    assert "expand" in found["gather"] and "expand" in found["scatter"]
    assert found["gather"] - {"model"} == {"gather", "expand", "apply"}
    assert found["scatter"] - {"model"} == {"expand", "apply"}
    assert found["dot"] <= {"model"}


def _step_as_pr38_built_it(tr):
    """The one-program step as the tree of PR 38 wrote it out, from the
    trainer's own pieces: the loss differentiated in the step's body, no
    function between them (``SparseTableCTRTrainer._make_step`` before the
    mesh step joined its row gradients itself)."""
    import jax.numpy as jnp
    import optax

    from lightctr_tpu.models.ctr_trainer import _health_pack, softmax_count_names
    from lightctr_tpu.models.sparse_trainer import _StepCounts
    from lightctr_tpu.ops import sparse_kernels
    from lightctr_tpu.utils.profiling import annotate

    armed = tr._quality_bins is not None
    seq = tr.cfg.loss == "softmax_xent"
    loss_fn = tr._make_loss_fn(with_probs=armed)
    spec, lane_pack = tr._spec, tr._lane_pack
    layout = _StepCounts(
        spec, {k: tr._table_shapes[k][0] for k in spec}, {}, lane_pack,
        softmax_count_names(tr.logits_fn) if seq else ())

    def step(params, opt_state, batch):
        tables, dense, batch2, uids, rows, distinct = tr._dedup_and_gather(
            spec, params, batch, None, {}, lane_pack)

        def loss_on(rows, dense):
            return loss_fn({**dense, **rows}, batch2)

        if armed or seq:
            (loss, aux), (g_rows, g_dense) = jax.value_and_grad(
                loss_on, argnums=(0, 1), has_aux=True)(rows, dense)
            probs, model_counts = (aux, None) if armed else (None, aux)
        else:
            loss, (g_rows, g_dense) = jax.value_and_grad(
                loss_on, argnums=(0, 1))(rows, dense)
            probs = model_counts = None
        with annotate("step/update"):
            gnorm = optax.global_norm((g_rows, g_dense))
            updates, new_dense_state = tr.tx.update(
                g_dense, opt_state["dense"], dense)
            dense = jax.tree_util.tree_map(
                lambda p, u: p + u.astype(p.dtype), dense, updates)
        new_accum = {}
        with annotate("sparse_tables/apply"):
            for k in spec:
                tables[k], accum, _ = sparse_kernels.merge_apply(
                    tables[k], opt_state["accum"].get(k), uids[k], g_rows[k],
                    None, lr=tr.cfg.learning_rate, eps=tr._eps,
                    pack=lane_pack.get(k, 1))
                if accum is not None:
                    new_accum[k] = accum
        with annotate("step/update"):
            health = tr._append_sketch(
                jnp.concatenate([_health_pack(loss, gnorm),
                                 layout.pack(distinct, uids, batch,
                                             model_counts)]),
                probs, batch2)
        return ({**dense, **tables},
                {"dense": new_dense_state, "accum": new_accum}, loss, health)

    return step


@pytest.mark.parametrize("build", [_widedeep, _fm, _kimi],
                         ids=["widedeep", "fm", "kimi_linear"])
def test_the_one_device_step_keeps_the_name_stacks_it_had(build):
    """With no mesh nothing wraps the loss: the compiled step's named
    instructions — opcode and name stack, each as often — are those of the
    step PR 38 built (``seq/kda/scan`` is read off a label cut at 120
    characters: PERF.md section 7, question 8)."""
    tr, batch, instructions = _built(build, False)
    assert tr.mesh is None
    text = jax.jit(_step_as_pr38_built_it(tr), donate_argnums=(0, 1)).lower(
        tr._params, tr._opt_state, tr._put(batch)).compile().as_text()
    assert collections.Counter(instructions) == collections.Counter(
        _named(text))
    assert not [name for _, name in instructions if "shard_map" in name]


# -- the host's boundary: ``trainer/input`` ----------------------------------


class _Counted(np.ndarray):
    """A host array that counts every read of its ``nbytes``."""

    reads = 0

    @property
    def nbytes(self):
        _Counted.reads += 1
        return super().nbytes


@pytest.fixture(scope="module")
def wd_step():
    tr, batch = _widedeep(None)
    tr.telemetry = obs.MetricsRegistry()
    tr.train_step(batch)                       # compiled once for the module
    return tr, {k: v.view(_Counted) for k, v in batch.items()}


@pytest.mark.parametrize("axes", [None, X4], ids=["one_device", "data2xembed2"])
def test_a_recorded_trainer_input_says_what_crossed_its_boundary(axes, wd_step):
    if axes:
        tr, batch = _widedeep(make_mesh(MeshSpec(**axes)))
    else:
        tr, batch = wd_step
    trace.configure()
    with obs.override(True), trace.override_rate(1.0):
        tr.train_step(batch)
    (span,) = [r for r in trace.finished() if r["name"] == "trainer/input"]
    trace.configure()
    assert span["attrs"] == {
        "arrays": len(batch),
        "bytes": sum(np.asarray(v).nbytes for v in batch.values()),
        "devices": 4 if axes else 1}


def test_an_unrecorded_step_constructs_no_span_and_counts_no_bytes(
        wd_step, monkeypatch):
    """``obs`` on, no sampling rate and no profiler session: every span of
    the step is the shared null context (counted, not timed)."""
    tr, batch = wd_step
    made = []
    init = trace._SpanCM.__init__
    monkeypatch.setattr(trace._SpanCM, "__init__",
                        lambda self, *a: (made.append(a[0]), init(self, *a))[1])
    _Counted.reads = 0
    with obs.override(True):
        assert not trace.enabled() and not trace.profiling()
        tr.train_step(batch)
    assert made == [] and _Counted.reads == 0
    # the same step recorded: the counting hooks do count
    trace.configure()
    with obs.override(True), trace.override_rate(1.0):
        tr.train_step(batch)
    trace.configure()
    assert "trainer/input" in made and _Counted.reads == len(batch)


# -- the compile cache keeps the names an executable was compiled with --------

_TWO_SCOPES = """
import jax, jax.numpy as jnp
from lightctr_tpu.utils.compile_cache import configure_compile_cache

configure_compile_cache()

def under(scope):
    def f(x):
        with jax.named_scope(scope):
            return jnp.sin(x) * 2
    return jax.jit(f).lower(jnp.ones((8, 128))).compile().as_text()

first, second = under("phase_a"), under("phase_b")
assert "phase_a" in first and "phase_b" in second and "phase_a" not in second
"""


def test_a_program_whose_scopes_alone_changed_is_not_served_from_the_cache(tmp_path):
    """Two programs that differ by a scope's name alone: JAX's default
    cache key leaves the metadata out and hands the second the first's
    executable, name stacks and all — a device trace of the new step would
    read the parent's names.  A process of its own: the cache is
    process-wide configuration."""
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               PYTHONPATH=os.path.dirname(os.path.dirname(__file__)))
    p = subprocess.run([sys.executable, "-c", _TWO_SCOPES], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
