"""The prefill kernel's block classes, counted on the host: pure arithmetic on
pads and geometry (ops.flash_attention.prefill_block_classes), so these run
in the fast tier; the kernel's own cases are in test_ops_flash.py."""
import jax.numpy as jnp
import numpy as np
import pytest

from vnsum_tpu.models.llama import prefill_attention_mask
from vnsum_tpu.ops import flash_attention
from vnsum_tpu.ops.flash_attention import (
    BLOCK_CLASSES,
    prefill_block_class_grid,
    prefill_block_classes,
)


@pytest.mark.parametrize("seed", range(8))
def test_block_classes_agree_with_the_dense_mask(seed):
    """prefill_block_classes is the kernel's own rule (_block_class): over
    random pads, offsets, windows and geometries, a cell it calls dead has
    no true element in the dense path's mask, and a cell it calls interior
    is whole and has no false one."""
    rng = np.random.default_rng(seed)
    for _ in range(25):
        bq, bk = (int(rng.choice([8, 16, 24, 32])) for _ in range(2))
        S = int(rng.integers(1, 70))
        off = int(rng.choice([0, 0, rng.integers(0, 90)]))
        C = off + S + int(rng.integers(0, 40))
        win = int(rng.choice([0, 0, rng.integers(1, 48)]))
        B = int(rng.integers(1, 4))
        pads = rng.integers(0, off + S + 1, size=B)
        pads[rng.integers(0, B)] = rng.choice([0, off + S, bk * 2, bq])
        pads = np.minimum(pads, off + S)
        grid, (gq, gk) = prefill_block_class_grid(
            pads, S, C, off, win, block_q=bq, block_k=bk
        )
        # one fixed shape (one compile), cut to this draw's: an element of
        # the mask depends on its own (query, slot, pad) alone
        mask = np.asarray(prefill_attention_mask(
            jnp.asarray(np.resize(pads, 3)), 160, 200
        ))[:B, off:off + S, :C]
        if win:
            mask = mask & (
                np.arange(C)[None, :] > off + np.arange(S)[:, None] - win
            )[None]
        counts = dict.fromkeys(BLOCK_CLASSES, 0)
        for (b, i, j), c in np.ndenumerate(grid):
            cell = mask[b, i * gq:(i + 1) * gq, j * gk:(j + 1) * gk]
            name = BLOCK_CLASSES[c]
            counts[name] += 1
            if name.startswith("dead"):
                assert not cell.any(), (seed, name, b, i, j)
            elif name == "interior":
                assert cell.shape == (gq, gk) and cell.all(), (seed, b, i, j)
        assert counts == prefill_block_classes(
            pads, S, C, off, win, block_q=bq, block_k=bk
        )


@pytest.mark.parametrize(
    "pad,live", [(6292, 6), (4792, 19), (3292, 30), (1792, 55), (8192, 0),
                 (250, 72), (0, 72)],
)
def test_block_classes_of_the_offline_cells_tail_chunks(pad, live):
    """The benchmark's map dispatch at the kernel's own geometry (G=4,
    hd=128: bq 512 / bk 1024; four 2048-query chunks over C=8448): of the
    72 cells under the diagonal a tail chunk's row has 6, 19, 30 or 55
    with anything to do."""
    assert flash_attention._block_geometry(2048, 8448, 4, 128) == (512, 1024)
    total = dict.fromkeys(BLOCK_CLASSES, 0)
    for lo in range(0, 8192, 2048):
        for name, n in prefill_block_classes(
            [pad], 2048, 8448, lo, 0, G=4, hd=128
        ).items():
            total[name] += n
    assert total["dead_causal"] == 72
    assert total["dead_pad"] + total["interior"] + total["edge"] == 72
    assert total["interior"] + total["edge"] == live
    if pad == 0:
        assert total["interior"] == 56


@pytest.mark.parametrize("G,hd,want,why", [
    (3, 128, (512, 2048), "the measured default"),
    (4, 128, (512, 1024), "G * bk held to 3 * 2048: Qwen3, Phi-4"),
    (2, 256, (512, 1024), "bk shrinks with the head size: Gemma3"),
    # SmallThinker's 28/4 heads: 7 * 1024 is over 3 * 2048 too, so the
    # per-head score temporaries of the static unroll push bk down to its
    # floor of 512 — the (512, 512) tile PR 34 measured at twice the cost a
    # score of (1024, 1024) on the latent kernel (PERF.md section 7)
    (7, 128, (512, 512), "G * bk held to 3 * 2048, bk at its floor"),
])
def test_block_geometry_by_group_size(G, hd, want, why):
    assert flash_attention._block_geometry(2048, 8448, G, hd) == want, why
    assert flash_attention._block_geometry(8192, 8448, G, hd) == want, why


@pytest.mark.parametrize("window", [0, 4096])
def test_block_classes_of_a_window_layer_at_g7(window):
    """The SmallThinker cell's map dispatch at the kernel's geometry for G=7
    (bq 512 / bk 512; four 2048-query chunks of a full row over C=8448): a
    global layer computes the 136 cells on and under the diagonal; a
    4096-window layer computes, of those, only the cells that reach into
    some query's window: 9 key blocks a query block past the eighth."""
    total = dict.fromkeys(BLOCK_CLASSES, 0)
    for lo in range(0, 8192, 2048):
        for name, n in prefill_block_classes(
            [0], 2048, 8448, lo, window, G=7, hd=128
        ).items():
            total[name] += n
    computed = total["interior"] + total["edge"]
    assert sum(total.values()) == 16 * 17 and total["dead_pad"] == 0
    if not window:
        assert computed == 136 and total["interior"] == 120
    else:
        # query block i (0..15) sees key blocks max(0, i - 8) .. i
        assert computed == sum(min(i, 8) + 1 for i in range(16)) == 108
        assert total["interior"] == 0     # a window layer masks every cell
