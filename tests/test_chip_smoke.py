"""chip_smoke.py on the host: its phases at toy shapes on the virtual CPU
mesh, its refusal to run off a TPU, the compile-cache helper, and the
lowering gate — every registered kernel must pass the
Pallas -> Mosaic lowering at a small and at the trainer's shape, which needs
no chip (``lower(lowering_platforms=("tpu",))`` lowers, it never compiles or
executes)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

import chip_smoke
from lightctr_tpu.ops import sparse_kernels as sk
from lightctr_tpu.utils import compile_cache

REPO_ROOT = Path(__file__).resolve().parents[1]

TOY = chip_smoke.Shape(
    fields=8, n_cat=5, vocab=1 << 10, dim=8, hidden=8, batch=64, steps=20,
    mesh_steps=4, score_rows=(1, 7, 24), flash_t=128, flash_d=16,
)


def test_phases_run_at_toy_shape_on_the_cpu_mesh(tmp_path):
    """data -> train -> serve -> the two four-device layouts: the loss
    falls, the scorer agrees with predict_proba, and both meshes track the
    one-device trajectory with the batch split and the tables placed as
    the smoke asserts on the chip."""
    lines = []
    caches = chip_smoke.phase_data(TOY, str(tmp_path), lines.append)
    trainer, train = chip_smoke.phase_train(TOY, caches["train"],
                                            lines.append)
    assert len(train["losses"]) == TOY.steps
    assert train["losses"][-1] < train["losses"][0]
    assert train["compilations_after_warmup"] == 0

    serve = chip_smoke.phase_serve(TOY, trainer, caches["eval"], lines.append)
    assert [r["rows"] for r in serve["requests"]] == list(TOY.score_rows)
    assert all(r["max_abs_err"] <= 2e-3 for r in serve["requests"])

    mesh = chip_smoke.phase_mesh(TOY, caches["train"], train["losses"],
                                 lines.append)
    gspmd, hybrid = mesh["mesh:data2xembed2"], mesh["mesh:data4"]
    assert gspmd["embed_rows_per_device"] == TOY.vocab // 2
    assert gspmd["batch_rows_per_device"] == TOY.batch // 2
    assert hybrid["embed_rows_per_device"] == TOY.vocab
    assert hybrid["batch_rows_per_device"] == TOY.batch // 4
    assert set(hybrid["exchange_policy"]) == {"w", "embed"}
    assert any(line.startswith("[serve]") for line in lines)


def test_script_refuses_to_run_off_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(REPO_ROOT / "chip_smoke.py")], env=env,
        cwd=str(REPO_ROOT), capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr and "nothing was run" in proc.stderr
    assert proc.stdout == ""  # no phase ran, no result was printed


def test_result_line_has_the_contract_keys_and_no_others():
    facts = dict(chip_smoke.device_facts(), platform="tpu")
    out = json.loads(chip_smoke.result_line(facts))
    assert out == {"ok": True, "device": {
        "platform": "tpu", "kind": facts["kind"], "count": len(jax.devices())}}
    assert isinstance(out["device"]["count"], int)
    assert "\n" not in chip_smoke.result_line(facts)


def test_compile_cache_helper_places_the_cache(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))

    # the environment places it: the code sets no directory at all
    monkeypatch.setenv(compile_cache.ENV, "/somewhere/fixed")
    assert compile_cache.default_cache_dir() is None
    compile_cache.configure_compile_cache()
    assert not [k for k, _ in calls if k.endswith("cache_dir")]

    # unset: <checkout>/.jax_cache, the same path twice in a row
    monkeypatch.delenv(compile_cache.ENV)
    del calls[:]
    want = str(REPO_ROOT / ".jax_cache")
    assert compile_cache.default_cache_dir() == want
    assert compile_cache.default_cache_dir() == want
    compile_cache.configure_compile_cache()
    assert [v for k, v in calls if k.endswith("cache_dir")] == [want]


# -- the lowering gate --------------------------------------------------------

SMALL = chip_smoke.Shape(vocab=1 << 12, batch=64, flash_t=256)


@pytest.mark.parametrize("shape", [SMALL, chip_smoke.Shape()],
                         ids=["small", "phase-a"])
def test_kernels_auto_selects_on_tpu_lower_for_tpu(shape):
    cases = chip_smoke.kernel_cases(shape)
    assert {c.kernel for c in cases} == set(sk.KERNELS) == {
        "quantize_pack", "quantize_pack_ef", "flash_attention"}
    for case in cases:
        jax.jit(case.pallas).trace(*case.specs).lower(
            lowering_platforms=("tpu",))
