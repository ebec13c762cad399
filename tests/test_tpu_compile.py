"""The main path's Pallas kernels and one whole round, compiled for a
TPU v5e chip that is described, not attached.

The TPU compiler refuses what interpret mode accepts: block shapes off
the (8, 128) tiling, too much VMEM, programs that do not fit the device.
These tests compile at the paper's ResNet-20 width (``d = 272,282`` over
61 leaves, ``n = 10`` clients, ``T = 8``, batch 64) and require the
Pallas call (``tpu_custom_call``) in each compiled program.  Nothing
runs, so they say nothing about results or times.

Only one process at a time may load the TPU library, so the topology is
described inside a module fixture, never at import; every test lives in
this one file so that a single worker loads it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

N, D = 10, 272_282  # paper ResNet-20: n clients, flat update width
HBM_BYTES = 16 * 2**30  # one TPU v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            try:
                desc = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Steer the ops wrappers onto their kernel branch, as on a TPU."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


F32, BF16, I8 = jnp.float32, jnp.bfloat16, jnp.int8
CONN = [((N, N), F32), ((N,), F32), ((N, N), F32)]  # A, tau_up, tau_dd
# ops wrapper -> its argument (shape, dtype)s at ResNet-20 width
KERNEL_CASES = {
    "fused_aggregate_f32": (ops.fused_aggregate, CONN + [((N, D), F32)]),
    "fused_aggregate_bf16": (ops.fused_aggregate, CONN + [((N, D), BF16)]),
    "row_stream_f32": (ops.row_stream, [((N,), F32), ((N, D), F32)]),
    "row_stream_int8": (ops.row_stream, [((N,), F32), ((N, D), I8)]),
    "fused_memory_update": (ops.fused_memory_update,
                            CONN + [((N, D), F32), ((N, D), F32)]),
    "fused_dequant_aggregate": (ops.fused_dequant_aggregate,
                                CONN + [((N, D), I8), ((N,), F32)]),
    # one cluster of all n clients (what chip_smoke.py runs), and two
    # clusters of 8, whose tiles accumulate across the cluster axis
    "block_fused_aggregate_C1m10": (
        ops.block_fused_aggregate,
        [((1, N, N), F32), ((N,), F32), ((1, N, N), F32), ((N, D), F32)]),
    "block_fused_aggregate_C2m8": (
        ops.block_fused_aggregate,
        [((2, 8, 8), F32), ((16,), F32), ((2, 8, 8), F32), ((16, D), F32)]),
}


@pytest.mark.parametrize("name", list(KERNEL_CASES))
def test_kernel_compiles_for_v5e(name, one_chip, on_tpu):
    fn, args = KERNEL_CASES[name]
    compiled = jax.jit(fn).lower(
        *(_spec(one_chip, s, dt) for s, dt in args)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name,kernel", [("fused_aggregate_f32", "fused_aggregate_pallas"),
                                         ("row_stream_f32", "row_stream_pallas")])
def test_kernel_instruction_keeps_its_name_for_v5e(name, kernel, one_chip, on_tpu):
    """The benchmark finds the aggregation kernel in a trace by the HLO
    instruction's name: the pallas_call's ``name=`` must keep it, under
    the round's ``fl.aggregate`` scope."""
    from repro.telemetry import op_scopes

    fn, args = KERNEL_CASES[name]

    def scoped(*a):
        with jax.named_scope("fl.aggregate"):
            return fn(*a)

    text = jax.jit(scoped).lower(
        *(_spec(one_chip, s, dt) for s, dt in args)).compile().as_text()
    found = {op: scope for op, scope in op_scopes(text).items()
             if op.split(".")[0] == kernel}
    assert found and set(found.values()) == {"fl.aggregate"}


def test_per_client_kernel_round_compiles_for_v5e(one_chip, on_tpu):
    from repro.configs import colrel_paper
    from repro.core.flatten import flat_spec
    from repro.fl.round import RoundConfig, make_round_fn
    from repro.models import build
    from repro.optim import sgd, sgd_momentum
    from repro.strategies import ColRelStrategy

    setup = colrel_paper.full()
    T, B = setup.local_steps, setup.batch_size
    bundle = build(setup.cnn)
    server = sgd_momentum(1.0, beta=setup.server_momentum)
    params = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    assert flat_spec(params).d == D and len(jax.tree.leaves(params)) == 61
    rc = RoundConfig(n_clients=N, local_steps=T,
                     aggregation=ColRelStrategy(fused="kernel"))
    fn = make_round_fn(bundle.loss_fn, sgd(setup.lr), server, rc)

    def place(tree):
        return jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype), tree)

    batches = {"images": _spec(one_chip, (N, T, B, 32, 32, 3)),
               "labels": _spec(one_chip, (N, T, B), jnp.int32)}
    compiled = jax.jit(fn).lower(
        place(params), place(jax.eval_shape(server.init, params)), (),
        batches, _spec(one_chip, (N,)), _spec(one_chip, (N, N)),
        _spec(one_chip, (N, N))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES


def test_ssd_intra_kernels_keep_their_names_under_model_ssd_for_v5e(one_chip, on_tpu):
    """The one-group SSD at granite-4.0-h-micro's widths (64 heads of 64,
    state 128, chunk 256; 10 rows of 512 tokens), rematerialized as the
    hybrid's sub-layers are: the fused intra-chunk kernel compiles
    forward and backward, and the benchmark's ``ssd_ms`` finds both
    custom calls under ``model.ssd``."""
    from repro.models import ssm
    from repro.telemetry import op_scopes
    from repro.telemetry import spans as names

    B, T, H, P, N, Q = 10, 512, 64, 64, 128, 256

    @jax.checkpoint
    def mixer(C, Bm, x, loga):
        with jax.named_scope(names.MODEL_SSD):
            return ssm.ssd_one_group(C, Bm, x, loga, chunk=Q)[0]

    def loss(*a):
        return jnp.sum(mixer(*a) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        _spec(one_chip, (B, T, N)), _spec(one_chip, (B, T, N)),
        _spec(one_chip, (B, T, H, P)), _spec(one_chip, (B, T, H))).compile().as_text()
    found = {(op.split(".")[0], scope) for op, scope in op_scopes(text).items()
             if op.startswith("ssd_intra_pallas")}
    assert found == {("ssd_intra_pallas", names.MODEL_SSD),
                     ("ssd_intra_pallas_bwd", names.MODEL_SSD)}
