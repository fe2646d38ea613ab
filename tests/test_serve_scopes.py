"""The layer scopes of taobao_ssa's serve step.

A profiler trace names each device op by its `op_name` path,
`jit(serve_step)/<scope>/...`; the benchmark's trace reduction splits device
time by the first scope. So every equation of the serve step lies under
exactly one of `embed`, `encoder` and `tower`, in fp32 and in int8.
"""
import jax
import pytest

LAYERS = ("embed", "encoder", "tower")


def _equations(jaxpr, stack=""):
    """(name stack from the step's top, primitive) of every equation,
    inner jaxprs (softmax's custom_jvp, ...) included."""
    for e in jaxpr.eqns:
        name = "/".join(s for s in (stack, str(e.source_info.name_stack)) if s)
        yield name, e.primitive.name
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _equations(sub, name)


@pytest.fixture(scope="module")
def reduced():
    from repro.launch.serve import make_serve_step, recsys_rules
    from repro.launch.train import make_data, sized_config
    from repro.models.common import init_params
    from repro.models.recsys import api as rec_api

    cfg = sized_config("taobao_ssa", "reduced")
    params = jax.eval_shape(lambda: init_params(rec_api.param_defs(cfg), jax.random.key(0)))
    batch = {k: v for k, v in next(make_data(cfg, 8)(0)).items() if k != "label"}
    return make_serve_step(cfg, recsys_rules()), params, batch


@pytest.mark.parametrize("weights", ["fp32", "int8"])
def test_every_serve_equation_lies_under_one_layer_scope(reduced, weights):
    from repro.core.quantization import quantize_tree

    step, params, batch = reduced
    if weights == "int8":
        params = jax.eval_shape(quantize_tree, params)
    outer = jax.make_jaxpr(step)(params, batch).jaxpr
    (call,) = outer.eqns  # the jitted step itself
    assert call.params["name"] == "serve_step"
    eqns = list(_equations(call.params["jaxpr"].jaxpr))
    layers = [name.split("/")[0] for name, _ in eqns]
    assert set(layers) == set(LAYERS), [e for e, l in zip(eqns, layers) if l not in LAYERS]
    # the history gathers (one per candidate row) count as embed
    gathers = [name for name, prim in eqns if prim == "gather"]
    assert gathers and all(n.startswith("embed") for n in gathers)
    assert {n.split("/")[1] for n, _ in eqns if n.startswith("encoder/")} == \
        {"block0", "block1", "pool"}
