"""The main-path Pallas kernels compile for a TPU v5e chip.

Nothing runs here: each test lowers a kernel exactly as ``System`` calls
it (``jax.vmap`` over the cores axis, replicated model state) at the
paper's per-core shapes, and compiles it for a *described* v5e chip with
the TPU compiler that ships with jax.  This is what catches blocks that
Mosaic cannot tile, operand types the MXU rejects and VMEM overruns —
none of which the CPU interpreter sees.

The topology is described inside the ``topo`` fixture, never at import:
only one process may load the TPU library at a time, and every test
worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.lut import build_sigmoid_lut
from repro.kernels import dispatch

CORES = 64                      # default PimConfig
SUSY_PC = 5_000_000 // CORES    # 78,125 rows per core
HIGGS_PC = -(-11_000_000 // CORES)   # 171,875 rows per core
EMB_ROWS = 1_048_576 // CORES   # 16,384 rows x 128 x 4 B = 8 MiB shard
EMB_DIM, EMB_BATCH = 128, 4096
TPU = dispatch.KernelBackend.PALLAS_TPU


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


def _per_core(local):
    """The cores axis as ``System._per_core`` traces it: sharded args
    mapped, the rest replicated."""
    def run(sharded, replicated):
        return jax.vmap(lambda *s: local(*s, *replicated))(*sharded)
    return run


def test_fx_matvec_vmapped_susy(one_chip):
    run = _per_core(lambda x, w: dispatch.launch(
        "fx_matvec", x, w, 10, backend=TPU))
    hlo = _compile(lambda x, w: run((x,), (w,)), one_chip,
                   ((CORES, SUSY_PC, 18), jnp.int32), ((18,), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_int_matmul(one_chip):
    hlo = _compile(lambda a, b: dispatch.launch("int_matmul", a, b,
                                                backend=TPU),
                   one_chip, ((256, 256), jnp.int8), ((256, 256), jnp.int8))
    assert "tpu_custom_call" in hlo


def test_kmeans_assign_vmapped_higgs(one_chip):
    run = _per_core(lambda x, c: dispatch.launch(
        "kmeans_assign", x, c, backend=TPU))
    hlo = _compile(lambda x, c: run((x,), (c,)), one_chip,
                   ((CORES, HIGGS_PC, 28), jnp.int16), ((16, 28), jnp.int16))
    assert "tpu_custom_call" in hlo


def test_gini_counts_vmapped_higgs(one_chip):
    max_nodes = 2 ** (4 + 2)    # TreeConfig(max_depth=4)
    run = _per_core(lambda x, y, leaf, th: dispatch.launch(
        "gini_split", x, y, leaf, th, 2, backend=TPU))
    hlo = _compile(lambda x, y, leaf, th: run((x, y, leaf), (th,)),
                   one_chip, ((CORES, HIGGS_PC, 28), jnp.float32),
                   ((CORES, HIGGS_PC), jnp.int32),
                   ((CORES, HIGGS_PC), jnp.int32),
                   ((max_nodes, 28), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_lut_sigmoid_vmapped_susy(one_chip):
    lut = build_sigmoid_lut(boundary=20, frac_bits=10)   # 20,480 entries
    assert lut.table.shape == (20_480,)
    run = _per_core(lambda z: dispatch.launch("lut_sigmoid", z, lut,
                                              backend=TPU))
    hlo = _compile(lambda z: run((z,), ()), one_chip,
                   ((CORES, SUSY_PC), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_emb_gather_vmapped_8mib_shard(one_chip, dtype):
    run = _per_core(lambda tab, ids, idx: dispatch.launch(
        "emb_gather", tab, ids, idx, backend=TPU))
    hlo = _compile(lambda tab, ids, idx: run((tab, ids), (idx,)), one_chip,
                   ((CORES, EMB_ROWS, EMB_DIM), dtype),
                   ((CORES, EMB_ROWS), jnp.int32),
                   ((EMB_BATCH,), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_emb_scatter_add_vmapped_8mib_shard(one_chip, dtype):
    run = _per_core(lambda tab, ids, idx, upd: dispatch.launch(
        "emb_scatter_add", tab, ids, idx, upd, backend=TPU))
    hlo = _compile(lambda tab, ids, idx, upd: run((tab, ids), (idx, upd)),
                   one_chip, ((CORES, EMB_ROWS, EMB_DIM), dtype),
                   ((CORES, EMB_ROWS), jnp.int32),
                   ((EMB_BATCH,), jnp.int32),
                   ((EMB_BATCH, EMB_DIM), dtype))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("op", ["gini_split", "emb_gather"])
def test_exact_dots_ignore_caller_precision(one_chip, op):
    """A float32 reference fit runs under default_matmul_precision
    ("highest"); the kernels' exact one-pass bfloat16 dots must not
    inherit it (Mosaic refuses float32 contraction of bf16 operands)."""
    if op == "gini_split":
        run = _per_core(lambda x, y, leaf, th: dispatch.launch(
            "gini_split", x, y, leaf, th, 2, backend=TPU))
        fn = lambda x, y, leaf, th: run((x, y, leaf), (th,))  # noqa: E731
        shapes = (((1, 8192, 28), jnp.float32), ((1, 8192), jnp.int32),
                  ((1, 8192), jnp.int32), ((64, 28), jnp.float32))
    else:
        run = _per_core(lambda tab, ids, idx: dispatch.launch(
            "emb_gather", tab, ids, idx, backend=TPU))
        fn = lambda tab, ids, idx: run((tab, ids), (idx,))  # noqa: E731
        shapes = (((1, 4096, EMB_DIM), jnp.float32), ((1, 4096), jnp.int32),
                  ((EMB_BATCH,), jnp.int32))
    with jax.default_matmul_precision("highest"):
        assert "tpu_custom_call" in _compile(fn, one_chip, *shapes)
