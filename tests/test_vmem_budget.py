"""Hardware-codesign checks: each Pallas kernel's per-grid-step VMEM
working set (blocks + scratch) must fit comfortably in TPU VMEM.

Budget: 16 MiB — conservative for v5e-class cores (real VMEM is larger,
but staying far under leaves room for double buffering, which the Pallas
pipeline emitter inserts automatically).  These are *static* checks on
the BlockSpec arithmetic — the structural analogue of the paper's WRAM
budget argument (the 40 KB LUT in a 64 KB scratchpad, Fig. 4).
"""
import jax.numpy as jnp

VMEM_BUDGET = 16 * 2 ** 20
DBL = 2  # double buffering factor on streamed blocks


def test_quant_matmul_vmem():
    bm = bn = bk = 128
    working = DBL * (bm * bk * 1 + bk * bn * 1)   # int8 in-blocks
    working += bm * bn * 4 * 2                    # int32 out + scratch acc
    assert working < VMEM_BUDGET
    assert working < 512 * 2 ** 10                # actually tiny: < 512 KiB


def test_flash_attention_vmem():
    bq = bk = 128
    d = 256                                       # generous head dim
    working = DBL * (bq * d + 2 * bk * d) * 2     # bf16 q/k/v blocks
    working += (bq * d + 2 * bq) * 4              # f32 acc + m + l scratch
    working += bq * d * 2                         # out block
    assert working < VMEM_BUDGET


def test_kmeans_assign_vmem():
    bn, f, k = 1024, 64, 64                       # generous upper bounds
    working = DBL * bn * f * 2                    # int16 point block
    working += k * f * 2                          # pinned centroids
    working += (2 * k * f + k + bn) * 4           # int32 sum pair/counts/labels
    assert working < VMEM_BUDGET


def test_gini_split_vmem():
    bn, f, L, C = 1024, 32, 64, 4
    working = DBL * (bn * f * 4 + bn * 8)         # f32 block + 2 int vecs
    working += L * f * 4                          # pinned thresholds
    working += (L * C * f + L * C) * 4            # count accumulators
    assert working < VMEM_BUDGET


def test_lut_sigmoid_vmem():
    """The paper's own budget argument: the 40 KB sigmoid table plus a
    streamed activation block fits any scratchpad tier.  On the TPU the
    table travels as int32 words of two entries for the lane gather (80
    x 128 words: the same 40 KB), pinned; the logits stream in blocks of
    at most BLOCK_ROWS rows, in and out, each double-buffered."""
    from repro.core.lut import build_sigmoid_lut
    from repro.kernels.lut_activation.kernel import (BLOCK_ROWS, LANES,
                                                     row_blocks,
                                                     table_words)
    lut = build_sigmoid_lut(boundary=20, frac_bits=10)
    table = lut.nbytes                            # = paper's 40 KB LUT
    assert table == 40 * 1024
    tab = table_words(lut.table)
    assert tab.shape == (80, LANES) and tab.dtype == jnp.int32
    assert tab.nbytes == table
    assert table_words(build_sigmoid_lut(frac_bits=8).table).shape == (
        20, LANES)
    pinned = DBL * tab.nbytes                     # the pipeline keeps two
    block = DBL * 2 * BLOCK_ROWS * LANES * 4      # int32 in + out blocks
    assert pinned + block < VMEM_BUDGET
    assert pinned + block < 4 * 2 ** 20
    # the SUSY (78,125) and Higgs (171,875) per-core logits: padded to a
    # multiple of 8 rows, never to a fixed block
    assert row_blocks(611) == (1, 616)
    assert row_blocks(1343) == (2, 672)
    assert row_blocks(611, block_rows=256) == (3, 208)
