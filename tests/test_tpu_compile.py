"""The main path's Pallas kernels compile for a TPU v5e chip.

Interpret mode (every other kernel test) never applies the TPU
compiler's rules, e.g. that the last two dims of a block must be
multiples of (8, 128) or equal the array's own dims.  These tests
compile each kernel of the trainer and serving paths at real widths
for a *described* v5e chip — the TPU compiler is installed even where
no chip is attached — and check that the program holds the kernel
(``tpu_custom_call``).  Nothing runs, so nothing here is a timing.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, and test workers
that each import this file must all collect the same tests.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import dasha_update as du
from repro.kernels import paged_attention as pa

# The fixture turns the persistent cache off; should an entry written
# for a described chip still be looked up, JAX warns that it cannot
# read it back and compiles again, which is harmless here.
pytestmark = pytest.mark.filterwarnings(
    "ignore:Error reading persistent compilation cache entry:UserWarning")

F32, I32 = jnp.float32, jnp.int32
D = 2048 * 8192          # one granite-3-2b MLP matrix, flattened
HP = dict(b=0.3, a=0.1, pa=0.5)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs in /tmp
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without the chip
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # no TPU compiler installed here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _kernel_cases():
    kb = (D // 128) // 64
    B, C, H, kvh, hd, P, M = 8, 16, 32, 8, 64, 16, 128
    NP = B * M

    def gqa(c):
        return (lambda q, k, v, t, s, ql: pa.paged_attention_batched_pallas(
                    q, k, v, t, s, ql, interpret=False),
                [((B, c, H, hd), F32), ((NP, P, kvh, hd), F32),
                 ((NP, P, kvh, hd), F32), ((B, M), I32), ((B,), I32),
                 ((B,), I32)])

    return {
        "dasha_update_batched": (
            lambda gn, go, h, gi, m: du.dasha_update_batched_pallas(
                gn, go, h, gi, m, interpret=False, **HP),
            [((1, D), F32)] * 4 + [((1,), F32)]),
        "dasha_h_update": (
            lambda gn, go, h, p: du.dasha_h_update_pallas(
                gn, go, h, p, b=0.3, pa=0.5, interpret=False),
            [((D,), F32)] * 3 + [((), F32)]),
        "blockrandk_payload_bs128": (
            lambda gn, go, h, gi, i: du.dasha_payload_blocks_pallas(
                gn, go, h, gi, i, scale=64.0, block_size=128,
                interpret=False, **HP),
            [((D,), F32)] * 4 + [((kb,), I32)]),
        "paged_gqa_decode": gqa(1),
        "paged_gqa_chunked_prefill": gqa(C),
        "paged_mla_r512_rope64": (
            lambda qa, qr, c, kr, t, s, ql: pa.paged_mla_attention_pallas(
                qa, qr, c, kr, t, s, ql, scale=0.07, interpret=False),
            [((B, C, 16, 512), F32), ((B, C, 16, 64), F32),
             ((NP, P, 512), F32), ((NP, P, 64), F32), ((B, M), I32),
             ((B,), I32), ((B,), I32)]),
    }


@pytest.mark.parametrize("name", sorted(_kernel_cases()))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _kernel_cases()[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text()
