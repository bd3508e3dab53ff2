"""The document-skipping attention core (``nn/mla.segment_attention``)
names itself in a device trace as the cores before it did: compiled on the
CPU at test size, every instruction of its block loops, forward and
backward, carries the caller's scope — ``seq/gqa/full`` in Mellum's full
layer, ``seq/mla/attention`` in Kimi's MLA layer — within the first 120
characters of the label a trace reader matches
(``benchmarks/harness/trace_reduce.op_label``); and Mellum's window
layers, the control, compile to the instructions they compiled to beside
the blocked core that multiplied every pair under the diagonal."""

import collections
import re

import jax
import jax.numpy as jnp
import pytest

from benchmarks.harness.trace_reduce import op_label
from lightctr_tpu import TrainConfig
from lightctr_tpu.data import ingest
from lightctr_tpu.models import kimi_linear, mellum
from lightctr_tpu.models.sparse_trainer import SparseTableCTRTrainer
from lightctr_tpu.nn import gqa, mla

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) ")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([a-z][a-z0-9\-]*)\(")
_CALLED = re.compile(r"(?:calls|body|condition)=%?([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# einsums only the new core writes (its forward's ``p v``, its backward's
# ``dO v^T``, ``dS^T q`` and ``p^T dO``)
_MARKS = ("jgqk,kjd->jgqd", "jgqd,kjd->jgqk", "jgqk,qjgd->kjd", "jgqk,jgqd->kjd")
# the longest instruction name a TPU trace put in front of a name stack
# (``multiply_reduce_fusion.33``), with room to spare
_NAME_ROOM = 32
# loop plumbing and the carries' zero fills, which take no device time
_PLUMBING = ("parameter", "constant", "get-tuple-element", "tuple", "bitcast", "broadcast")


def _computations(text):
    """``{computation: [(instruction, opcode, op_name, [called])]}``."""
    out, current = {}, None
    for line in text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            current = _COMPUTATION.match(line).group(1)
            out[current] = []
            continue
        ins = _INSTRUCTION.match(line)
        if ins and current:
            name = _OP_NAME.search(line)
            out[current].append((ins.group(1), ins.group(3),
                                 name.group(1) if name else None,
                                 _CALLED.findall(line)))
    return out


def _core_instructions(text):
    """The instructions of the core's block loops: the loops whose body
    makes one of its einsums (through the fusions it calls), the loops
    whose body holds such a loop, and everything their bodies call."""
    comps = _computations(text)

    def reach(name, through_loops):
        seen, todo = set(), [name]
        while todo:
            c = todo.pop()
            if c in seen or c not in comps:
                continue
            seen.add(c)
            for _, op, _, called in comps[c]:
                if through_loops or op != "while":
                    todo.extend(called)
        return seen

    def whiles_in(names):
        return {(c, i) for c in names for i, op, _, _ in comps[c] if op == "while"}

    def body_of(comp, inst):
        return [called for i, _, _, called in comps[comp] if i == inst][0]

    marked = set()
    for comp, inst in whiles_in(comps):
        body = set().union(*(reach(c, False) for c in body_of(comp, inst)))
        if any(n and n.endswith(m + "/dot_general") for c in body
               for _, _, n, _ in comps[c] for m in _MARKS):
            marked.add((comp, inst))
    outer = {(comp, inst) for comp, inst in whiles_in(comps)
             if any(w in marked for w in whiles_in(
                 set().union(*(reach(c, False) for c in body_of(comp, inst)))))}
    core = set()
    for comp, inst in marked | outer:
        for c in body_of(comp, inst):
            core |= reach(c, True)
    return [(i, op, n) for c in core for i, op, n, _ in comps[c] if n]


def _timed(core):
    return [(i, op, n) for i, op, n in core
            if op not in _PLUMBING and not i.startswith("wrapped_broadcast")
            and not (op == "copy" and "closed_call/" not in n)]


def _step_text(model):
    params, logits = model.build(jax.random.PRNGKey(0))
    tr = SparseTableCTRTrainer(
        params, logits,
        TrainConfig(learning_rate=0.05, lambda_l2=0.0, loss="softmax_xent"),
        sparse_tables={"embed": ["tokens"]})
    docs = [list(range(1, 20)), list(range(5, 40)), list(range(30, 50))]
    batch = ingest.sequence_batch(ingest.pack_documents(docs, 32))
    return jax.jit(tr._build_step(), donate_argnums=(0, 1)).lower(
        tr._params, tr._opt_state, tr._put(batch)).compile().as_text()


@pytest.mark.parametrize("model, scope, within", [
    (mellum, "seq/gqa/full", 120 - _NAME_ROOM),
    # Kimi's MLA stacks end their scope 86 to 123 characters in, as the
    # blocked core's did (checkpoint, head-group loop, remat around it):
    # no reader reads them yet
    (kimi_linear, "seq/mla/attention", None),
], ids=["mellum-full", "kimi_linear-mla"])
def test_every_instruction_of_the_core_carries_its_scope(model, scope, within):
    core = _core_instructions(_step_text(model))
    forward = [n for _, _, n in core if "transpose(" not in n]
    backward = [n for _, _, n in core if "transpose(" in n]
    assert forward and backward
    assert any(m in n for n in forward for m in _MARKS[:1])
    assert any(m in n for n in backward for m in _MARKS[1:])
    # a transform may close around the stack's first scope: jvp(seq/gqa)/full
    found = re.compile(r"\)*/".join(map(re.escape, scope.split("/"))))
    for inst, op, name in _timed(core):
        at = found.search(name)
        assert at, (op, name)
        if within:
            assert at.end() <= within, (op, name)
            assert found.search(op_label(inst, name).replace("_/", ")/")), (op, name)


def _blocked_over_every_earlier_key(q, k, v, seg, block):
    """``segment_attention`` before this core: a query block against every
    key up to its end, Python-unrolled, each block made again in the
    backward pass."""
    out = []
    for first in range(0, q.shape[0], block):
        last = min(first + block, q.shape[0])
        out.append(jax.checkpoint(mla._block, static_argnums=(5,))(
            q[first:last], k[:last], v[:last], seg[first:last], seg[:last], first))
    return jnp.concatenate(out, axis=0)


def test_the_window_layers_compile_as_beside_the_blocked_core(monkeypatch):
    def window_instructions():
        """``(opcode, shape, name stack)`` of each instruction under the
        window layers' scope, as often as it occurs."""
        found = collections.Counter()
        for line in _step_text(mellum).splitlines():
            ins, name = _INSTRUCTION.match(line), _OP_NAME.search(line)
            if ins and name and "seq/gqa/window" in name.group(1):
                found[(ins.group(3), ins.group(2), name.group(1))] += 1
        return found

    new = window_instructions()
    monkeypatch.setattr(gqa, "segment_attention", lambda q, k, v, seg, block: jax.vmap(
        lambda *a: _blocked_over_every_earlier_key(*a, block))(q, k, v, seg))
    jax.clear_caches()                   # a checkpointed layer's trace is cached
    old = window_instructions()
    jax.clear_caches()
    assert new and old == new
