"""Compile a benchmark configuration's training step for a described TPU
v5e, with no chip attached, and say what the compiler made of the sparse
phases.

  JAX_PLATFORMS=cpu python -m tools.aot_step --config criteo-fm-k64
  JAX_PLATFORMS=cpu python -m tools.aot_step --config criteo-widedeep \\
      --vocab 67108864 --mesh data=2,embed=2 --hlo /root/scratch/x4.hlo

The step is the one the benchmark's cell runs:
``benchmarks/models/<model>.build_trainer`` builds the program's trainer
on a tiny table, ``_build_step()`` hands back its step function, and that
is lowered at the configuration's real shapes for
``topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")``
with ``donate_argnums=(0, 1)``, as the trainer jits it.  Nothing runs: the
report is the compiler's memory reckoning and four lists read off the
optimized HLO (docs/KERNELS.md, "Reading tools/aot_step.py"):

  - ``memory``: ``compiled.memory_analysis()`` (arguments, outputs,
    aliased bytes, temporaries, generated code);
  - ``table_copies``: every ``copy`` whose result has a table's shape —
    the shape the step holds it in, ``[V // r, 128]`` for a lane-packed
    table — with the computation it sits in (the entry, a loop body, one
    branch of a conditional): a full-table copy is 17.5 ms a step at
    7*2^21 x 64;
  - ``sorts``: every ``sort`` with its key count and JAX scope;
  - ``scatters`` / ``gathers``: the fusions (or bare ops) that hold a
    scatter or a gather of a table, with scope and computation.

stdout is the report as JSON; the same, as a table, goes to stderr.  A
compile the chip's compiler would refuse (out of HBM) exits 1 with the
compiler's words.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import re
import sys
from typing import Dict, List, Optional

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

TOPOLOGY = "v5e:2x2"

_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(?[a-z0-9]+\[[^=]*?)\s([a-z\-]+)\(")
_SHAPE = re.compile(r"[a-z0-9]+\[([0-9,]*)\]")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_LOOP = re.compile(r"\b(body|condition)=%?([\w.\-]+)")


def _dims(type_text: str) -> List[tuple]:
    """Every array shape in an instruction's result type (a tuple type
    holds several)."""
    return [tuple(int(d) for d in m.split(",") if d)
            for m in _SHAPE.findall(type_text)]


def parse_hlo(text: str) -> List[Dict]:
    """One record per instruction of an HLO module's text: ``name``,
    ``op``, ``type`` (the result type as written, layout included),
    ``shapes``, ``scope`` (the JAX name stack), ``computation`` (named by
    its role where it has one: ``branch 3 of cond.85``, ``body of
    while.2``), ``calls`` (the fused computation, for a fusion)."""
    out, comp, role = [], None, {}
    for line in text.splitlines():
        h = _HEADER.match(line)
        if h:
            comp = ("ENTRY " if h.group(1) else "") + h.group(2)
            continue
        m = _INSTR.match(line)
        if not m or comp is None:
            continue
        name, type_text, op = m.groups()
        scope = _OP_NAME.search(line)
        calls = _CALLS.search(line)
        if op == "conditional":
            for n, b in enumerate(_BRANCHES.search(line).group(1).split(",")):
                role[b.strip().lstrip("%")] = f"branch {n} of {name}"
        elif op == "while":
            for what, c in _LOOP.findall(line):
                role[c] = f"{what} of {name}"
        out.append({
            "name": name, "op": op, "type": type_text.strip(),
            "shapes": _dims(type_text),
            "scope": scope.group(1) if scope else "",
            "computation": comp,
            "calls": calls.group(1) if calls else None,
        })
    for i in out:
        i["computation"] = role.get(i["computation"], i["computation"])
    return out


def summarize_hlo(text: str, table_shapes: List[tuple]) -> Dict:
    """The four lists of the module docstring, from optimized HLO text.
    ``table_shapes``: the per-device shapes of the sparse tables."""
    instrs = parse_hlo(text)
    tables = {tuple(s) for s in table_shapes}
    by_comp: Dict[str, List[Dict]] = {}
    for i in instrs:
        by_comp.setdefault(i["computation"], []).append(i)

    def line(i: Dict) -> Dict:
        return {"name": i["name"], "type": i["type"], "scope": i["scope"],
                "computation": i["computation"]}

    def holds(i: Dict, op: str) -> bool:
        """``i`` is an ``op`` on a table, or a fusion whose body has one."""
        body = by_comp.get(i["calls"], []) if i["op"] == "fusion" else [i]
        return any(b["op"] == op for b in body) and (
            op == "gather" or bool(tables & set(i["shapes"])))

    fused = {i["calls"] for i in instrs if i["op"] == "fusion"}
    top = [i for i in instrs if i["computation"] not in fused]
    return {
        "table_copies": [line(i) for i in instrs if i["op"] == "copy"
                         and tables & set(i["shapes"])],
        "sorts": [dict(line(i), keys=max((s[-1] if s else 1)
                                         for s in i["shapes"]))
                  for i in instrs if i["op"] == "sort"],
        "scatters": [line(i) for i in top if holds(i, "scatter")],
        "gathers": [line(i) for i in top if holds(i, "gather")
                    and any(len(s) >= 1 and s[0] > 1024 for s in i["shapes"])],
    }


def _memory(compiled) -> Dict[str, int]:
    m = compiled.memory_analysis()
    return {k: int(getattr(m, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "alias_size_in_bytes", "temp_size_in_bytes",
        "generated_code_size_in_bytes")}


def _host_batch(cfg: Dict) -> Dict:
    """A batch in the generator's columns, run through the model's own
    host layout so the step sees the keys the cell feeds it."""
    import numpy as np

    b, f = cfg["batch"], cfg["fields"]
    fields = np.tile(np.arange(f, dtype=np.int32), (b, 1))
    return {"fids": fields.copy(), "fields": fields,
            "vals": np.ones((b, f), np.float32),
            "mask": np.ones((b, f), np.float32),
            "labels": np.zeros((b,), np.float32)}


def compile_step(cfg: Dict, mesh_axes: Optional[Dict[str, int]] = None):
    """``(compiled, table_shapes)``: the configuration's step, compiled for
    the described v5e — one chip, or ``mesh_axes`` over its four."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)

    jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])
    model = importlib.import_module("benchmarks.models." + cfg["model"])
    key = jax.random.PRNGKey(0)
    if hasattr(model, "aot_trainer"):
        # a model whose dense leaves are GBs too builds its trainer small
        # and hands over the step of the real sizes
        trainer = model.aot_trainer(cfg)
    else:
        tiny = dict(cfg, vocab=1024)
        trainer = model.build_trainer(tiny, model.init_params(tiny, key))

    topo = topologies.get_topology_desc(platform="tpu", topology_name=TOPOLOGY)
    specs = model.param_specs(cfg)
    if mesh_axes:
        shape = tuple(mesh_axes.values())
        mesh = Mesh(np.array(topo.devices).reshape(shape), tuple(mesh_axes))
        shards = int(mesh_axes.get("embed", 1))

        def place(axes=()):
            return NamedSharding(mesh, P(*axes))

        batch_axes = ("data",) if "data" in mesh_axes else ()
        # the step reads which tables are row-sharded, and over what, off
        # the trainer: hand it the described mesh (no array can be put on
        # a described device, so the constructor could not take it)
        trainer.mesh = mesh
        trainer._param_sharding = jax.tree_util.tree_map(
            place, specs, is_leaf=lambda x: isinstance(x, tuple))
    else:
        one, shards = SingleDeviceSharding(topo.devices[0]), 1

        def place(axes=()):
            return one

        batch_axes = ()
    step = trainer._build_step()

    def struct(x, axes=()):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=place(axes))

    shapes = jax.eval_shape(lambda k: model.init_params(cfg, k), key)
    # a table the trainer keeps lane-packed is [V // r, 128] to the step
    # (docs/KERNELS.md, "The lane-packed store"): the rule read shapes on
    # the tiny table, and holds at the real one where it divides alike
    for k, r in trainer._lane_pack.items():
        v, d = shapes[k].shape
        shapes[k] = jax.ShapeDtypeStruct((v // r, r * d), shapes[k].dtype)
    params = jax.tree_util.tree_map(
        struct, shapes, specs, is_leaf=lambda x: isinstance(x, tuple))
    tables = list(trainer._opt_state["accum"])
    dense = jax.eval_shape(
        trainer.tx.init, {k: v for k, v in shapes.items() if k not in tables})
    opt = {"dense": jax.tree_util.tree_map(struct, dense),
           "accum": {k: params[k] for k in tables}}
    host_batch = getattr(model, "aot_batch", _host_batch)(cfg)
    batch = {k: struct(v, batch_axes)
             for k, v in model.feed_layout(cfg, host_batch).items()}
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        params, opt, batch).compile()
    per_device = [(shapes[k].shape[0] // shards,) + shapes[k].shape[1:]
                  for k in tables]
    return compiled, per_device


def _print_table(report: Dict, out) -> None:
    gb = 1e9
    mem = report["memory"]
    print("memory (GB): " + "  ".join(
        f"{k.replace('_size_in_bytes', '')} {v / gb:.3f}"
        for k, v in mem.items()), file=out)
    for section in ("table_copies", "sorts", "scatters", "gathers"):
        rows = report[section]
        print(f"{section}: {len(rows)}", file=out)
        for r in rows:
            keys = f" keys={r['keys']}" if "keys" in r else ""
            print(f"  {r['name']:28s} {r['type'][:48]:48s}{keys}  "
                  f"in {r['computation']}  [{r['scope'][-70:]}]", file=out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True,
                    help="a name under benchmarks/configs/ (or a path)")
    ap.add_argument("--vocab", type=int, help="override the table's rows")
    ap.add_argument("--mesh", help="e.g. data=2,embed=2 (default: one chip)")
    ap.add_argument("--hlo", metavar="PATH",
                    help="also write the optimized HLO text there")
    ap.add_argument("--json", action="store_true", help="no table on stderr")
    args = ap.parse_args(argv)

    path = args.config if os.path.exists(args.config) else os.path.join(
        REPO_ROOT, "benchmarks", "configs", args.config + ".json")
    with open(path) as f:
        cfg = json.load(f)
    if args.vocab:
        cfg["vocab"] = args.vocab
    mesh_axes = None
    if args.mesh:
        mesh_axes = {k: int(v) for k, v in
                     (part.split("=") for part in args.mesh.split(","))}
    try:
        compiled, table_shapes = compile_step(cfg, mesh_axes)
    except Exception as e:  # the compiler's refusal is the finding
        if "RESOURCE_EXHAUSTED" not in str(e) and "hbm" not in str(e).lower():
            raise
        print(f"COMPILE FAILED: {str(e)[:12000]}", file=sys.stderr)
        return 1
    text = compiled.as_text()
    if args.hlo:
        with open(args.hlo, "w") as f:
            f.write(text)
    report = {"config": os.path.basename(path), "vocab": cfg["vocab"],
              "mesh": mesh_axes, "topology": TOPOLOGY,
              "memory": _memory(compiled),
              **summarize_hlo(text, table_shapes)}
    if not args.json:
        _print_table(report, sys.stderr)
    print(json.dumps(report, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
