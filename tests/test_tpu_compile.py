"""Compiles for a described TPU v5e: the Pallas kernels and the DistilBERT
client step at published widths, with no chip attached.

The TPU compiler is installed with jax; it compiles for a chip that is
described (``v5e:2x2``) and not attached, and refuses what the chip would
refuse: an illegal block shape, an op Mosaic cannot lower, a program that
does not fit device memory.  Nothing here runs; the tests read the compiled
program's text and memory analysis.

The topology is described inside the ``topo`` fixture only (never at
import), because one process at a time may load the TPU library and a test
worker that loses that race must still collect the same tests.  The fixture
also turns the persistent compilation cache off: a program compiled for a
described chip is written to it but cannot be read back without one.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 * 2**30          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", was_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=sharding),
        tree)


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _kernel_case(name):
    """(callable, abstract args) of one kernel at a config's real shapes."""
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.mamba2_scan import mamba2_scan
    from repro.kernels.moe_gmm import moe_ffn
    from repro.kernels.rwkv6_scan import rwkv6_scan
    f32, bf16 = jnp.float32, jnp.bfloat16
    if name == "flash_attention/distilbert":     # encoder: 12 heads of 64
        qkv = _sds((32, 128, 12, 64), f32)
        return (lambda q, k, v: flash_attention(q, k, v, causal=False,
                                                interpret=False),
                (qkv, qkv, qkv))
    if name == "flash_attention/qwen2-7b":       # GQA 28/4, head 128
        kv = _sds((1, 2048, 4, 128), bf16)
        return (lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                interpret=False),
                (_sds((1, 2048, 28, 128), bf16), kv, kv))
    if name == "moe_gmm/olmoe-1b-7b":            # 64 experts, d 2048, f 1024
        return (lambda *a: moe_ffn(*a, interpret=False),
                (_sds((64, 160, 2048), bf16), _sds((64, 2048, 1024), bf16),
                 _sds((64, 2048, 1024), bf16), _sds((64, 1024, 2048), bf16)))
    if name == "rwkv6_scan/rwkv6-1.6b":          # 32 heads of 64
        x = _sds((1, 1024, 32, 64), bf16)
        return (lambda *a: rwkv6_scan(*a, interpret=False),
                (x, x, x, x, _sds((32, 64), bf16),
                 _sds((1, 32, 64, 64), f32)))
    if name == "mamba2_scan/zamba2-1.2b":        # 64 heads of 64, state 64
        bc = _sds((1, 1024, 64), bf16)
        return (lambda *a: mamba2_scan(*a, interpret=False),
                (_sds((1, 1024, 64, 64), bf16), _sds((1, 1024, 64), bf16),
                 _sds((64,), f32), bc, bc, _sds((1, 64, 64, 64), f32)))
    raise KeyError(name)


@pytest.mark.parametrize("name", [
    "flash_attention/distilbert", "flash_attention/qwen2-7b",
    "moe_gmm/olmoe-1b-7b", "rwkv6_scan/rwkv6-1.6b",
    "mamba2_scan/zamba2-1.2b"])
def test_kernel_compiles_to_mosaic(one_chip, name):
    fn, args = _kernel_case(name)
    compiled = jax.jit(fn).lower(*_on(one_chip, args)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _distilbert_step(impl, head_capacity=None):
    from repro import optim
    from repro.configs import get_config
    from repro.core.strategy import make_strategy
    from repro.models.steps import abstract_train_state
    from repro.telemetry.step import train_batch_struct
    cfg = get_config("distilbert-mlm")
    opt = optim.adam(5e-5)
    step = make_strategy("fedavg").make_client_step(
        cfg, opt, impl=impl, head_capacity=head_capacity)
    params, opt_state = abstract_train_state(cfg, opt)
    return step, (params, opt_state, train_batch_struct(cfg, 32, 128))


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_distilbert_client_step_fits_one_chip(one_chip, monkeypatch, impl):
    """The full-width client step (6 layers, d 768, vocab 30522, Adam,
    batch 32 x seq 128) compiles for v5e and fits its HBM.  Under
    ``impl="pallas"`` the attention is the Mosaic kernel: the test steers
    ``kernels.ops.interpret_mode`` to the chip's answer, since this process
    only sees the CPU."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    step, args = _distilbert_step(impl)
    compiled = jax.jit(step).lower(*_on(one_chip, args)).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES
    assert ("tpu_custom_call" in compiled.as_text()) == (impl == "pallas")


def test_distilbert_step_flops_match_cpu_count(one_chip):
    """The round ledger's dot/conv FLOPs read the same program compiled for
    the chip and for the CPU: the TPU compiler writes batched matmuls as
    lhs-dilated convolutions, which must count once per real tap."""
    from repro.telemetry.cost import analyze
    step, args = _distilbert_step("xla")
    cpu = analyze(jax.jit(step).lower(*args).compile().as_text()).dot_flops
    step, args = _distilbert_step("xla")
    tpu = analyze(jax.jit(step).lower(*_on(one_chip, args)).compile()
                  .as_text()).dot_flops
    assert tpu == pytest.approx(cpu, rel=0.10)


def test_distilbert_gathered_head_step_fits_one_chip(one_chip):
    """The client step with the LM head at 768 gathered rows of the 4,096
    (the capacity of 15% masks at 32 x 128) compiles for v5e, needs less
    memory than the head at every position, and its dot FLOPs drop by the
    head's forward and backward at the 3,328 rows it no longer runs: the
    MLM transform (d x d) and the tied projection (d x vocab)."""
    from repro.telemetry.cost import analyze
    got = {}
    for cap in (None, 768):
        step, args = _distilbert_step("xla", head_capacity=cap)
        compiled = jax.jit(step).lower(*_on(one_chip, args)).compile()
        got[cap] = (analyze(compiled.as_text()).dot_flops,
                    compiled.memory_analysis().temp_size_in_bytes)
    d, vocab = 768, 30522
    assert got[None][0] - got[768][0] == pytest.approx(
        3 * 2 * (4096 - 768) * d * (d + vocab), rel=0.02)
    assert got[768][1] < got[None][1]
