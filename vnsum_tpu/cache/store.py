"""Paged KV block store + the engine-facing PrefixCache facade.

The pool mirrors the stacked cache layout the attention kernels consume
(models/llama.py init_kv_cache: [L, B, KV, C, hd], scales [L, B, KV, C]):
one pool row per block, [L, KV, BLK, hd] (and [L, KV, BLK] for int8-KV
scales; 64-wide heads two a lane tile as the cache keeps them, [L, KV/2,
BLK, 128] — the pool moves whole slots and never looks inside a tile), so
extraction and gather are pure layout-preserving copies — no
transpose ever materializes on device.

Blocks are POSITION-CONTIGUOUS: a block holds the KV of BLK consecutive
prompt tokens at RoPE positions [off, off + BLK), independent of where the
row sat in its producer batch. Left-padded batches place token position p of
a row at cache slot pad + p (models/llama.py prefill_positions), so a block
extracted at slot pad_src + off pastes into any consumer row at slot
pad_dst + off — the positions line up by construction, which is what makes
cross-request, cross-bucket reuse sound.

Two device ops, both jitted per cache-shape bucket:

- :meth:`BlockStore.write_blocks` — copy a list of block slabs out of a
  batch cache into the pool (insertion after prefill): ONE dispatch per
  call, whatever the list's length. The (row, slot, block) triples cross as
  one fixed-length host vector with the true count beside it, and the
  program copies slab by slab in a loop of that many trips, in list order,
  with the donated pool as the carry — so there is one executable per cache
  shape, no slab-sized temporary beside the pool, a block id that occurs
  twice keeps its later write, and no copy can clamp onto neighbouring
  slots.
- :meth:`BlockStore.gather` — vmapped per-row ``dynamic_update_slice`` of up
  to NB blocks into a fresh batch cache at per-row slot offsets (the same
  per-row ragged-write shape as llama._cache_write's vector path). Rows
  needing fewer blocks pad with the scratch block id; those writes land at
  slots the suffix prefill overwrites (or a filler row nobody reads), so
  padding is harmless by construction — see backend/engine.py's resume path
  for the slot arithmetic that guarantees it.
"""
from __future__ import annotations

import numpy as np

from .radix import Match, RadixIndex


def _pow2_at_least(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


class BlockStore:
    """Device pool of ``num_blocks`` KV blocks (+1 scratch row used as the
    padding target for ragged gathers; the radix index never hands it out).

    Under a ``mesh`` the pool shards its KV-head dim over `model` —
    mirroring ``parallel.sharding.cache_specs`` so gather/extract copies are
    head-local (no resharding collective on the hot path) — and stays
    replicated over `data`: a block is position-contiguous KV shared by ALL
    batch rows, so every data replica must see every block."""

    def __init__(
        self,
        num_blocks: int,
        block_tokens: int,
        *,
        n_layers: int,
        n_kv_heads: int,
        head_dim: int,
        dtype,
        quantized: bool = False,
        mesh=None,
    ) -> None:
        import jax.numpy as jnp

        self.block_tokens = block_tokens
        self.scratch_id = num_blocks
        self.mesh = mesh
        N = num_blocks + 1
        model_size = 1 if mesh is None else mesh.shape.get("model", 1)
        # the batch cache's own tiling of its heads (models/llama.py
        # init_kv_cache): 64-wide heads two a lane tile, the scales a head
        from ..ops.flash_attention import heads_per_lane_tile

        tile = heads_per_lane_tile(n_kv_heads, head_dim, model_size)
        heads = (N, n_layers, n_kv_heads, block_tokens)
        shape = (N, n_layers, n_kv_heads // tile, block_tokens,
                 head_dim * tile)
        # [N, L, KV(, BLK, hd)]: KV heads over `model`, rest replicated —
        # allocated DIRECTLY into the sharding (a production pool is sized
        # against the mesh's combined HBM; materializing it on one chip
        # first would OOM at exactly the scale the mesh exists for)
        placement = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ..parallel.mesh import AXES

            if n_kv_heads % max(model_size, 1):
                raise ValueError(
                    f"n_kv_heads={n_kv_heads} is not divisible by mesh axis "
                    f"'{AXES.model}' ({model_size}); shrink that axis or "
                    "pick a TP-compatible model config"
                )

            def placement(ndim):
                return NamedSharding(
                    mesh,
                    P(*((None, None, AXES.model) + (None,) * (ndim - 3))),
                )

        def zeros(shp, dt):
            if placement is None:
                return jnp.zeros(shp, dt)
            return jnp.zeros(shp, dt, device=placement(len(shp)))

        if quantized:
            self.pool = {
                "k": zeros(shape, jnp.int8),
                "v": zeros(shape, jnp.int8),
                "ks": zeros(heads, jnp.float32),
                "vs": zeros(heads, jnp.float32),
            }
        else:
            self.pool = {
                "k": zeros(shape, dtype),
                "v": zeros(shape, dtype),
            }
        self._write_fns: dict = {}
        self._gather_fns: dict = {}
        # dispatches of the write program; with the radix's inserted_blocks
        # it says how many blocks one dispatch carries (/metrics)
        self.write_dispatches = 0

    @property
    def hbm_bytes(self) -> int:
        return sum(v.size * v.dtype.itemsize for v in self.pool.values())

    @staticmethod
    def _shape_sig(cache: dict) -> tuple:
        return tuple(sorted((k, v.shape, str(v.dtype)) for k, v in cache.items()))

    def _constrain_batch_cache(self, cache: dict) -> dict:
        """Pin a [L, B, KV, C(, hd)] batch cache to the engine's (data,
        model) layout inside a traced gather — without this the seeded
        cache's layout is left to GSPMD propagation and the resume prefill
        pays a re-layout on its first touch. Identity off-mesh."""
        if self.mesh is None:
            return cache
        import jax
        from jax.sharding import NamedSharding

        from ..parallel.sharding import cache_specs

        specs = cache_specs(quantized="ks" in cache)
        return {
            name: jax.lax.with_sharding_constraint(
                buf, NamedSharding(self.mesh, specs[name])
            )
            for name, buf in cache.items()
        }

    # -- insertion -------------------------------------------------------

    def write_blocks(self, cache: dict, triples) -> None:
        """Copy, for each (row, slot, block_id) of ``triples`` in order, the
        [slot, slot+BLK) slab of batch ``row`` of ``cache`` ([L, B, KV,
        C(, hd)] leaves) into pool block ``block_id``: one dispatch for the
        whole list, none for an empty one. The list is padded to a length
        fixed by the cache's shape (no row holds more than C // BLK whole
        blocks) and the loop's trip count is an input, so every list of one
        cache shape runs the same executable."""
        import jax

        n = len(triples)
        if n == 0:
            return
        BLK = self.block_tokens
        key = self._shape_sig(cache)
        _, B, _, C = next(iter(cache.values())).shape[:4]
        idx = np.zeros((B * (C // BLK), 3), dtype=np.int32)
        if n > len(idx):
            raise ValueError(
                f"{n} blocks to write from a cache of {B} rows x {C} slots "
                f"(at most {len(idx)} whole {BLK}-token blocks)"
            )
        idx[:n] = triples
        fn = self._write_fns.get(key)
        if fn is None:

            def write(pool, cache, idx, n):
                def write_one(i, pool):
                    row, slot, bid = idx[i, 0], idx[i, 1], idx[i, 2]
                    out = {}
                    for name, buf in cache.items():
                        # [L, B, KV, C(, hd)] -> slab [L, KV, BLK(, hd)]
                        L, _, KV = buf.shape[:3]
                        tail = buf.shape[4:]
                        sizes = (L, 1, KV, BLK) + tail
                        starts = (0, row, 0, slot) + (0,) * len(tail)
                        slab = jax.lax.dynamic_slice(buf, starts, sizes)[:, 0]
                        out[name] = jax.lax.dynamic_update_slice(
                            pool[name], slab[None],
                            (bid,) + (0,) * (pool[name].ndim - 1),
                        )
                    return out

                return jax.lax.fori_loop(0, n, write_one, pool)

            fn = jax.jit(write, donate_argnums=(0,))
            self._write_fns[key] = fn
        self.pool = fn(self.pool, cache, idx, np.int32(n))
        self.write_dispatches += 1

    # -- gather ----------------------------------------------------------

    def gather(self, cache: dict, block_ids: np.ndarray, starts: np.ndarray) -> dict:
        """Seed ``cache`` (a fresh [L, B, KV, C, hd] batch cache) with pool
        blocks: row b gets block_ids[b, i] written at slot starts[b] + i*BLK.
        ``block_ids`` is [B, NB'] (any NB'); it is padded to a power-of-two
        NB with the scratch id to bound compiled-program fan-out."""
        import jax
        import jax.numpy as jnp

        BLK = self.block_tokens
        B, nb = block_ids.shape
        NB = _pow2_at_least(max(nb, 1))
        ids = np.full((B, NB), self.scratch_id, dtype=np.int32)
        ids[:, :nb] = block_ids
        key = (B, NB, self._shape_sig(cache))
        fn = self._gather_fns.get(key)
        if fn is None:

            # in-place block writes on the cache in its own layout: a loop
            # over block positions, rows unrolled inside. Not a vmap over
            # the batch axis — that moves axis 1 to the front and back, and
            # on the chip those are whole-cache transposes (see
            # models.llama._cache_write)
            def gather_fn(pool, cache, ids, starts):
                def write_position(i, cache):
                    cache = dict(cache)
                    for b in range(B):
                        for name, buf in cache.items():
                            blk = pool[name][ids[b, i]]  # [L, KV, BLK(, hd)]
                            idx = (0, b, 0, starts[b] + i * BLK) + (0,) * (
                                buf.ndim - 4
                            )
                            cache[name] = jax.lax.dynamic_update_slice(
                                buf, blk[:, None], idx
                            )
                    return cache

                out = jax.lax.fori_loop(0, NB, write_position, cache)
                return self._constrain_batch_cache(out)

            fn = jax.jit(gather_fn, donate_argnums=(1,))
            self._gather_fns[key] = fn
        return fn(
            self.pool, cache, jnp.asarray(ids),
            jnp.asarray(starts, dtype=jnp.int32),
        )


class PrefixCache:
    """Radix index + block store, the one object the engine talks to.

    Single engine thread does all mutation (match-with-pin, gather, insert);
    other threads may only :meth:`probe` — the contract inherited from
    cache/radix.py."""

    def __init__(
        self,
        num_blocks: int,
        block_tokens: int,
        *,
        n_layers: int,
        n_kv_heads: int,
        head_dim: int,
        dtype,
        quantized: bool = False,
        mesh=None,
    ) -> None:
        self.block_tokens = block_tokens
        self.index = RadixIndex(num_blocks, block_tokens)
        self.store = BlockStore(
            num_blocks, block_tokens, n_layers=n_layers,
            n_kv_heads=n_kv_heads, head_dim=head_dim, dtype=dtype,
            quantized=quantized, mesh=mesh,
        )

    def match(self, ids, max_tokens: int | None = None) -> Match:
        return self.index.match(ids, max_tokens)

    def release(self, match: Match) -> None:
        self.index.release(match)

    def probe(self, ids, max_tokens: int | None = None) -> int:
        return self.index.probe(ids, max_tokens)

    def gather(self, cache: dict, block_ids, starts) -> dict:
        return self.store.gather(cache, block_ids, starts)

    def insert(self, cache: dict, rows) -> int:
        """Index tokens[:upto] of each freshly prefilled row of ``rows`` —
        (row, slot_base, ids, upto), the row sitting left-padded at
        ``slot_base`` in ``cache`` — and copy all their newly allocated
        blocks' KV out of ``cache`` in ONE dispatch, in the rows' order: a
        later row's insert may evict a leaf an earlier row just allocated
        (pins last only for a row's own insert), the block id then occurs
        twice and the later write wins. Returns the number of new blocks."""
        triples = [
            (row, slot_base + off, block)
            for row, slot_base, ids, upto in rows
            for block, off in self.index.insert(ids, upto)
        ]
        self.store.write_blocks(cache, triples)
        return len(triples)

    def stats_dict(self) -> dict:
        d = self.index.stats_dict()
        d["block_tokens"] = self.block_tokens
        d["hbm_bytes"] = self.store.hbm_bytes
        d["write_dispatches"] = self.store.write_dispatches
        return d
