"""Compiles for a described TPU v5e chip, and the chip smoke's rehearsal.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these tests need no chip. They catch what
interpret mode cannot: block shapes the chip's layout refuses, and
programs that do not fit its memory. The topology is described only inside
the `topo` fixture, so importing or collecting this file never loads the
TPU library; where it cannot be described, the compile tests skip.
"""
import functools
import importlib.util
import json
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = Path(__file__).resolve().parents[1]
HBM_BYTES = 16 * 2**30  # one v5e chip
B = 512
L = 100  # taobao_ssa history length
D = 64  # taobao_ssa attention / item width


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile against
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent cache
    # but not read back, so the cache stays off around these compiles
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)


@pytest.fixture(scope="module")
def published(one_chip):
    """taobao_ssa at its published widths, as shapes placed on one chip."""
    from repro.configs.base import get_config
    from repro.launch.serve import recsys_rules
    from repro.launch.train import make_data
    from repro.models.common import init_params
    from repro.models.recsys import api as rec_api

    cfg = get_config("taobao_ssa")
    params = jax.eval_shape(
        lambda: init_params(rec_api.param_defs(cfg), jax.random.key(0)))
    batch = next(make_data(cfg, B)(0))
    return {"cfg": cfg, "rules": recsys_rules(), "params": _on(params, one_chip),
            "batch": _on(batch, one_chip)}


def _fits(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < HBM_BYTES


@pytest.mark.parametrize("variant", ["baseline", "quantized"])
def test_serve_step_compiles_at_published_widths(published, one_chip, variant):
    from repro.core.quantization import quantize_tree
    from repro.launch.serve import make_serve_step

    params = published["params"]
    if variant == "quantized":
        params = _on(jax.eval_shape(quantize_tree, params), one_chip)
    batch = {k: v for k, v in published["batch"].items() if k != "label"}
    step = make_serve_step(published["cfg"], published["rules"])
    _fits(step.lower(params, batch).compile())


@pytest.mark.parametrize("variant,rows", [("baseline", 4096), ("quantized", 256)])
def test_compact_serve_step_compiles_at_published_widths(published, one_chip, variant,
                                                         rows):
    """Each distinct history once: the encoder and the history gathers are
    sized by the capacity, never by the rows."""
    from repro.core.quantization import quantize_tree
    from repro.launch.serve import ROWS_PER_HISTORY, make_serve_step

    params = published["params"]
    if variant == "quantized":
        params = _on(jax.eval_shape(quantize_tree, params), one_chip)
    cap = rows // ROWS_PER_HISTORY
    per_row = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip)
    batch = {"user": per_row, "item": per_row, "category": per_row, "hist_row": per_row,
             "hist_block": jax.ShapeDtypeStruct((cap, 2 * L + 1), jnp.int32,
                                                sharding=one_chip)}
    layout = (("hist_item", (L,)), ("hist_category", (L,)), ("hist_len", ()))
    step = make_serve_step(published["cfg"], published["rules"])
    compiled = step.jitted.lower(params, batch, layout=layout).compile()
    _fits(compiled)
    shapes = [tuple(map(int, s.split(",")))
              for s in re.findall(r"= \w+\[([\d,]+)\]", compiled.as_text())]
    assert [s for s in shapes if s[0] == cap and L in s[1:]]  # the histories, once
    assert not [s for s in shapes if (s[0] == rows and L in s[1:]) or s[0] == rows * L]


def test_train_step_compiles_at_published_widths(published, one_chip):
    from repro.models.recsys import api as rec_api
    from repro.training.optimizer import get_optimizer
    from repro.training.train_loop import make_train_step

    cfg, rules = published["cfg"], published["rules"]
    opt = get_optimizer("adamw", 1e-3)
    state = _on(jax.eval_shape(opt.init, published["params"]), one_chip)
    step = jax.jit(make_train_step(lambda p, b: rec_api.loss(p, b, cfg, rules), opt))
    _fits(step.lower(published["params"], state, published["batch"]).compile())


def _kernels():
    from repro.kernels.block_pruned_matmul.block_pruned_matmul import block_pruned_matmul
    from repro.kernels.fm_interaction.fm_interaction import fm_interaction_kernel
    from repro.kernels.int8_matmul.int8_matmul import int8_matmul
    from repro.kernels.local_attention.local_attention import local_attention

    i8, f32, i32 = jnp.int8, jnp.float32, jnp.int32
    return {
        # the encoder FFN over B*L history rows, and the tower padded to blocks
        "int8_matmul_ffn_in": (functools.partial(int8_matmul, bk=D),
                               [((B * L, D), i8), ((D, 4 * D), i8),
                                ((B * L,), f32), ((4 * D,), f32)]),
        "int8_matmul_ffn_out": (functools.partial(int8_matmul, bn=D),
                                [((B * L, 4 * D), i8), ((4 * D, D), i8),
                                 ((B * L,), f32), ((D,), f32)]),
        "int8_matmul_tower": (int8_matmul,
                              [((B, 256), i8), ((256, 256), i8),
                               ((B,), f32), ((256,), f32)]),
        "block_pruned_matmul_ffn_in": (functools.partial(block_pruned_matmul, bk=D),
                                       [((B * L, D), f32), ((D, 4 * D), f32),
                                        ((1, 2), i32)]),
        "block_pruned_matmul_ffn_out": (functools.partial(block_pruned_matmul, bn=D),
                                        [((B * L, 4 * D), f32), ((4 * D, D), f32),
                                         ((2, 1), i32)]),
        # 4 heads of 16 over the 100-step history, one block per sequence
        "local_attention": (functools.partial(local_attention, window=32, bq=L, bk=L),
                            [((B * 4, L, 16), f32)] * 3),
        # the fm config: 39 fields of width 10
        "fm_interaction": (functools.partial(fm_interaction_kernel, bb=256),
                           [((B, 39, 10), f32)]),
    }


@pytest.mark.parametrize("name", [
    "int8_matmul_ffn_in", "int8_matmul_ffn_out", "int8_matmul_tower",
    "block_pruned_matmul_ffn_in", "block_pruned_matmul_ffn_out",
    "local_attention", "fm_interaction",
])
def test_kernel_compiles_for_the_chip(one_chip, name):
    fn, shapes = _kernels()[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # a Mosaic kernel, not interpreted


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_rehearsal(capsys):
    smoke = _chip_smoke()
    assert smoke.main(["--rehearse"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    assert [line.split(":")[0] for line in lines[:-1]] == [
        "device", "model", "train", "ladder", "serve", "precision",
        "calibrate_smoke", "compile_cache"]
    train = json.loads(lines[2].split(": ", 1)[1])
    assert train["steps_taken"] == smoke.TRAIN_STEPS == len(train["losses"])


def test_chip_smoke_refuses_a_cpu_as_the_chip(capsys):
    smoke = _chip_smoke()
    with pytest.raises(smoke.SmokeFailure, match="needs 'tpu'"):
        smoke.main([])
    assert '"ok"' not in capsys.readouterr().out
