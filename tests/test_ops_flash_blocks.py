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
    # SmallThinker's 28/4 heads. A group wider than 4 loops over its heads,
    # so the tile ONE head computes no longer shrinks with the group: the
    # cell's map dispatch, kernel alone on the v5e, took 2.42 s at the
    # (512, 512) the unroll's rule gave it, 1.55 at (512, 1024), 1.71 at
    # (512, 2048) and 1.44 at (1024, 1024), two heads a loop step (PERF.md
    # section 6, PR 36)
    (7, 128, (1024, 1024), "fastest for the dispatch: 1.44 s for 2.42"),
    (8, 128, (1024, 1024), "64/8 heads: the looped tile, 41 MiB counted"),
    (12, 128, (1024, 1024), "51 MiB counted, Mosaic needs 42"),
    (16, 128, (1024, 1024), "61 MiB of the 64 the kernel may ask for"),
    (32, 128, (512, 1024), "1024 query rows of 32 heads do not fit: 512"),
    (8, 256, (1024, 512), "a looped group's key width shrinks with hd too"),
])
def test_block_geometry_by_group_size(G, hd, want, why):
    assert flash_attention._block_geometry(2048, 8448, G, hd) == want, why
    assert flash_attention._block_geometry(8192, 8448, G, hd) == want, why


def test_a_group_that_fits_no_geometry_raises_with_the_numbers():
    """64 query heads a KV head: the q and o tiles and the softmax state of
    64 x 512 rows alone pass the 64 MiB the kernel may ask for."""
    with pytest.raises(ValueError, match=(
        r"G=64, head_dim=128, bq=512, bk=1024 need 95420416 bytes of 67108864"
    )):
        flash_attention._block_geometry(2048, 8448, 64, 128)
    # the counter's view (no kernel to compile) still has its geometry
    assert flash_attention._block_geometry(
        2048, 8448, 64, 128, interpret=True) == (512, 1024)


@pytest.mark.parametrize("G,hd,bq,bk,mosaic_mib", [
    (3, 128, 512, 2048, 22), (4, 128, 512, 1024, 14), (7, 128, 1024, 1024, 34),
    (8, 128, 1024, 1024, 33), (12, 128, 1024, 1024, 42),
    (16, 128, 1024, 1024, 53), (32, 128, 512, 1024, 47),
    (8, 256, 1024, 512, 41), (4, 64, 512, 1024, 13), (2, 256, 512, 1024, 14),
])
def test_vmem_count_stands_above_what_mosaic_needs(G, hd, bq, bk, mosaic_mib):
    """_vmem_bytes against the least scoped VMEM the chip's compiler took
    for the kernel at a bf16 cache (bisected to the MiB: the looped groups
    in PR 36; the unrolled ones in PR 45, whose order keeps two heads'
    score tiles alive — 22 and 14 MiB where one took 20 and 13): never under
    it, at most three tenths over."""
    counted = flash_attention._vmem_bytes(G, hd, bq, bk) / 2**20
    assert mosaic_mib <= counted <= 1.3 * mosaic_mib


@pytest.mark.parametrize("window", [0, 4096])
def test_block_classes_of_a_window_layer_at_g7(window):
    """The SmallThinker cell's map dispatch at the kernel's geometry for G=7
    (bq 1024 / bk 1024; four 2048-query chunks of a full row over C=8448): a
    global layer computes the 36 cells on and under the diagonal, 28 of
    them with no mask; a 4096-window layer computes, of those, the cells
    that reach into some query's window — 5 key blocks a query block past
    the fourth — and masks only the two a window's edge or the diagonal
    crosses: the cells wholly inside every query's window are interior."""
    assert flash_attention._block_geometry(2048, 8448, 7, 128) == (1024, 1024)
    total = dict.fromkeys(BLOCK_CLASSES, 0)
    for lo in range(0, 8192, 2048):
        for name, n in prefill_block_classes(
            [0], 2048, 8448, lo, window, G=7, hd=128
        ).items():
            total[name] += n
    computed = total["interior"] + total["edge"]
    assert sum(total.values()) == 8 * 9 and total["dead_pad"] == 0
    if not window:
        assert computed == 36 and total["interior"] == 28
    else:
        # query block i (0..7) sees key blocks max(0, i - 4) .. i; the
        # diagonal's is edge, and so is block i - 4, which the window's
        # floor crosses (slot 1024 (i - 4) is outside row 1024 i's window,
        # slot 1024 (i - 4) + 1 inside it)
        assert computed == sum(min(i, 4) + 1 for i in range(8)) == 30
        assert total["edge"] == 8 + 4
        assert total["interior"] == sum(min(i, 3) for i in range(8)) == 18
