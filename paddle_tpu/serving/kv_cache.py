"""Paged KV-cache manager for the continuous-batching scheduler.

The pool is a fixed-shape BLOCK ARENA `(layers, 1, num_blocks, heads,
block_size, row_width)` plus one page table `(num_slots, max_pages)`
int32. What a row holds is the served model's business, described by its
`serving.model.CacheSpec`: a GPT's is a head's K and V side by side
(`heads` of `2*head_dim`), a latent-attention model's is ONE compressed
row a token shared by all its heads (`heads` = 1). Nothing here reads a
row; slots, blocks, refcounts, hashes, swap payloads and migration
tickets index axis 2 and are the same for both. A "slot" is one
sequence's page-table row, and its rows live
scattered across arena blocks (vLLM-style PagedAttention). Fixed shapes
are still the whole point — XLA compiles ONE decode executable over the
arena + page table (batch dim = num_slots, always) and one prefill per
SUFFIX bucket, so compile count stays O(buckets), never O(requests) —
but HBM is now paid per PAGE, not per worst-case context: a 10-token
request holds one block, not max_len rows, so concurrent capacity is
bounded by actual tokens resident, not by num_slots × max_len.

On top of the allocator sits a HASHED PREFIX CACHE: prompt prefixes are
hashed at block granularity (a chained blake2b per full block), and a
new admission whose leading blocks match cached ones maps those blocks
into its page row (refcounted) instead of re-prefilling them — identical
system prompts are computed and stored ONCE. Blocks whose refcount drops
to zero but that still carry a registered hash go to an LRU pool: they
keep serving hits until arena pressure evicts them (deepest-prefix
blocks first). Copy-on-write discipline: only blocks FULLY covered by
the shareable prompt region (never the block holding position p_len-1,
which the decode tail writes into) are ever shared, so the first block a
request writes is private by construction and two requests sharing a
prefix can never see each other's divergence.

CACHE GROUPS (serving/model.py): a model whose layers keep different
state (layers that attend over a window beside layers that attend over
everything) names several CacheSpecs. The first is the PRIMARY group and
is everything described above; each further group is a `_GroupPool`: an
arena and a free list of its own, and a page row a slot that follows the
primary's in the SAME page table (`group_layout[i].columns`). A window
group's row is a RING of `ring_pages` blocks fixed at admission: a slot
never holds more than `window + block_size` rows of it, nothing is freed
before the slot is. Admission needs every pool to have the blocks; a
model with more than one group takes no prefix hits and cannot be
swapped out (one payload carries one group). A STATE group (a fixed-size
state a slot: serving/model.py) is a `_GroupPool` like any other whose
page row is ONE column: the slot's one block, of the group's own shape
and type (a float32 arena beside a bfloat16 one), claimed at admission and
released with the slot. Its allocation is recorded under the span
`serving/state_alloc`.

Block index 0 is the reserved SCRATCH block (of every group's arena): never allocated, it absorbs
the in-graph ride-along writes of frozen slots (see
the models' decode steps) and the page-row padding past a sequence's tail.

Host-side bookkeeping (slots/blocks/refcounts/hashes) lives here; the
arena itself is a jax value the scheduler threads through its jitted
dispatches and stores back (`self.kv`), next to the device-resident page
table the scheduler owns.

DONATION DISCIPLINE: the scheduler donates the arena AND the device page
table into every prefill and fused decode dispatch (`donate_argnums`),
so the buffers behind consumed values are reused in place by XLA and the
donated-in arrays are DEAD afterwards. Never cache a reference to
`cache.kv` (or the scheduler's page table) across a scheduler step —
re-read the attribute; the scheduler always stores the dispatch's output
back before returning.
"""

from __future__ import annotations

import hashlib
import math
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..observability.tracer import get_tracer

__all__ = ["ShapeBuckets", "SlotKVCache"]


class ShapeBuckets:
    """The small fixed set of padded prompt lengths prefill compiles for.

    bucket_for(n) returns the smallest bucket >= n; a prompt longer than
    the largest bucket is a caller error (the engine validates at
    submit), so admission can never trigger an unplanned compile."""

    def __init__(self, sizes: Sequence[int]):
        sizes = sorted(set(int(s) for s in sizes))
        if not sizes:
            raise ValueError("ShapeBuckets needs at least one size")
        if sizes[0] < 1:
            raise ValueError(f"bucket sizes must be >= 1, got {sizes[0]}")
        self.sizes: Tuple[int, ...] = tuple(sizes)

    def __len__(self):
        return len(self.sizes)

    def __iter__(self):
        return iter(self.sizes)

    @property
    def max(self) -> int:
        return self.sizes[-1]

    def bucket_for(self, n: int) -> int:
        for s in self.sizes:
            if s >= n:
                return s
        raise ValueError(
            f"prompt length {n} exceeds the largest prefill bucket "
            f"{self.sizes[-1]}")


SCRATCH_BLOCK = 0


class _GroupPool:
    """A further cache group's blocks: an arena, a free list and every
    slot's page row (`layout.pages` entries, all claimed at admission and
    released with the slot). No sharing, no hashes: a block belongs to
    one slot. A window group's row is a ring of blocks of rows; a STATE
    group's is one block, the slot's state (`spec.state`: `blocks_for` is
    1 whatever the length, the arena has the spec's block shape and, where
    it names one, the spec's type)."""

    def __init__(self, layout, num_slots, block_size, num_blocks, alloc,
                 dtype):
        import jax.numpy as jnp
        self.layout = layout
        self.block_size = int(block_size)
        if layout.spec.dtype is not None:
            dtype = jnp.dtype(layout.spec.dtype)
        self.dtype = dtype
        if num_blocks is None:
            num_blocks = num_slots * layout.pages + 1
        self.num_blocks = int(num_blocks)
        if self.num_blocks < 2:
            raise ValueError(
                f"cache group {layout.spec.name!r}: num_blocks must be >= "
                f"2 (scratch + 1), got {num_blocks}")
        shape = layout.spec.arena_shape(self.num_blocks, self.block_size)
        self.kv = alloc(shape, self.dtype)
        self.pool_bytes = math.prod(shape) * self.dtype.itemsize
        self._free_blocks = list(range(self.num_blocks - 1, 0, -1))
        self._slot_blocks: List[List[int]] = [[] for _ in range(num_slots)]
        self.peak_blocks_used = 0

    @property
    def blocks_total(self) -> int:
        return self.num_blocks - 1

    @property
    def blocks_used(self) -> int:
        return self.blocks_total - len(self._free_blocks)

    def blocks_for(self, positions: int) -> int:
        """Blocks a slot of `positions` positions holds of this group:
        its pages, never more than the ring's."""
        return min((positions - 1) // self.block_size + 1, self.layout.pages)

    def can_hold(self, positions: int) -> bool:
        return self.blocks_for(positions) <= len(self._free_blocks)

    def claim(self, slot: int, positions: int) -> List[int]:
        blocks = [self._free_blocks.pop()
                  for _ in range(self.blocks_for(positions))]
        self._slot_blocks[slot] = blocks
        self.peak_blocks_used = max(self.peak_blocks_used, self.blocks_used)
        return blocks

    def release(self, slot: int) -> None:
        self._free_blocks.extend(reversed(self._slot_blocks[slot]))
        self._slot_blocks[slot] = []

    def held_rows(self, slot: int) -> int:
        """Rows this group can hold of the slot: its blocks' worth."""
        return len(self._slot_blocks[slot]) * self.block_size

    def occupancy(self) -> Dict[str, object]:
        spec = self.layout.spec
        out = {"name": spec.name, "window": spec.window,
               "layers": spec.layers, "pages_a_slot": self.layout.pages,
               "blocks_total": self.blocks_total,
               "blocks_used": self.blocks_used,
               "peak_blocks_used": self.peak_blocks_used,
               "pool_bytes": self.pool_bytes}
        if spec.state:
            out.update(state=True, dtype=str(self.dtype),
                       bytes_a_slot=self.block_bytes)
        return out

    @property
    def block_bytes(self) -> int:
        """One block over every layer of the group: what a slot holds of
        a state group."""
        return self.pool_bytes // self.num_blocks


class SlotKVCache:
    """Paged block arena + slot/page allocator + hashed prefix cache.

    kv: (layers, 1, num_blocks, heads, block_size, row_width) — the block
    arena, shaped by the served model's CacheSpec (block 0 is scratch, never allocated). A slot is a page-table
    row of up to max_pages block ids; admission maps exactly the pages a
    request's prompt+budget needs (`blocks_for(p_len + max_new)`), so
    the arena packs short requests densely instead of paying max_len per
    slot. `length(slot)` still tracks live positions for occupancy
    reporting.

    num_blocks defaults to slab-equivalent capacity (num_slots ×
    max_pages + scratch) so a paged pool is a drop-in replacement; size
    it DOWN (or num_slots UP) to oversubscribe worst-case contexts —
    admission falls back to queueing when pages run out."""

    def __init__(self, cfg, num_slots: int, max_len: int, dtype=None,
                 block_size: int = 16, num_blocks=None,
                 prefix_cache: bool = True, mesh_shards: int = 1,
                 arena_device=None, kv_dtype: Optional[str] = None):
        import jax.numpy as jnp

        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if max_len < 1:
            raise ValueError(f"max_len must be >= 1, got {max_len}")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        # tensor-parallel shard count of the arena (mesh_shape=(tp,)):
        # blocks/slots/refcounts are LOGICAL whole-arena units (each
        # block's heads are split across chips, so the allocator is
        # mesh-oblivious), but BYTES gauges must be per-chip-aware —
        # reporting whole-arena pool_bytes as if one chip held it is
        # exactly the operator-facing bug the hbm_per_chip_bytes split
        # fixes.
        if mesh_shards < 1:
            raise ValueError(
                f"mesh_shards must be >= 1, got {mesh_shards}")
        # deferred like the scheduler's: a model's module must not be
        # imported during package import
        from .model import cache_groups, serving_model
        # the model's cache groups in one page table; the first is the
        # primary, which everything below this block is about
        self.group_layout = cache_groups(serving_model(cfg), cfg, max_len,
                                         block_size)
        spec = self.group_layout[0].spec
        grouped = len(self.group_layout) > 1
        if grouped and (mesh_shards != 1 or kv_dtype is not None):
            raise ValueError(
                "a model with several cache groups is served on one chip "
                "from full-precision arenas: mesh_shards and kv_dtype "
                "carry one group")
        # `num_blocks`: the primary pool's, or one a group
        if isinstance(num_blocks, (tuple, list)):
            if len(num_blocks) != len(self.group_layout):
                raise ValueError(
                    f"num_blocks names {len(num_blocks)} pools, the model "
                    f"has {len(self.group_layout)} cache groups")
            group_blocks = list(num_blocks[1:])
            num_blocks = num_blocks[0]
        else:
            group_blocks = [None] * (len(self.group_layout) - 1)
        if spec.heads % mesh_shards:
            raise ValueError(
                f"the arena's {spec.heads} heads are not divisible by "
                f"mesh_shards {mesh_shards} — the heads axis shards "
                "evenly or not at all")
        self.spec = spec
        self.mesh_shards = int(mesh_shards)
        self.cfg = cfg
        self.num_slots = int(num_slots)
        self.max_len = int(max_len)
        self.block_size = int(block_size)
        self.max_pages = -(-self.max_len // self.block_size)  # ceil
        if num_blocks is None:
            num_blocks = self.num_slots * self.max_pages + 1
        self.num_blocks = int(num_blocks)
        if self.num_blocks < 2:
            raise ValueError(
                f"num_blocks must be >= 2 (scratch + 1), got {num_blocks}")
        # a hit of n blocks is valid for a window group only with the
        # window's rows before it: a model with further groups takes none
        self.prefix_cache_enabled = bool(prefix_cache) and not grouped
        # kv_dtype: the arena STORAGE discipline — None keeps the
        # compute-dtype slab ("float32"/"bfloat16" pool), "int8" packs
        # one byte per K/V value plus a per-(block, head, row) f32
        # scale plane (the model quantizes at the scatter and
        # dequantizes at the gather). Anything else is a loud config error —
        # there is no silent fp32 fallback for an unknown dtype.
        if kv_dtype not in (None, "int8"):
            raise ValueError(
                f"unknown kv_dtype {kv_dtype!r}: expected None "
                "(full precision) or 'int8'")
        self.kv_quantized = kv_dtype == "int8"
        if self.kv_quantized:
            self.dtype = jnp.dtype(jnp.int8)
        else:
            self.dtype = jnp.dtype(dtype) if dtype is not None \
                else jnp.dtype(jnp.float32)
        shape = spec.arena_shape(self.num_blocks, self.block_size)
        scale_shape = shape[:-1] + (2,)
        # arena_device (a jax sharding/device or None = default): the
        # arena must be ALLOCATED under its mesh sharding, not
        # allocated whole and resharded after — allocate-then-move
        # would transiently pin the full pool_bytes on one chip at
        # construction, defeating exactly the per-chip capacity win a
        # sharded pool exists for (invisible on CPU, an OOM on real
        # chips sized near per-chip HBM)
        def alloc(shp, dt):
            return jnp.zeros(shp, dt) if arena_device is None \
                else jnp.zeros(shp, dt, device=arena_device)

        self.kv = alloc(shape, self.dtype)
        # the scale plane shards on the heads axis alongside the data
        # (same PartitionSpec prefix — dim 3), so quantize/dequant stay
        # chip-local on a tp mesh
        self.kv_scales = alloc(scale_shape, jnp.float32) \
            if self.kv_quantized else None
        # constant for the engine's life (donation reuses the buffer in
        # place every dispatch) — computed ONCE from the ACTUAL arena
        # itemsize(s), never an assumed fp32: an int8 pool is data
        # bytes + its f32 scale plane, a quarter-ish of the slab a
        # dtype-blind formula would report
        self._pool_bytes = math.prod(shape) * self.dtype.itemsize
        if self.kv_quantized:
            self._pool_bytes += math.prod(scale_shape) * 4
        # the further groups' pools (none for a model with one group)
        self._pools: List[_GroupPool] = [
            _GroupPool(layout, self.num_slots, self.block_size, nb, alloc,
                       self.dtype)
            for layout, nb in zip(self.group_layout[1:], group_blocks)]
        self._pool_bytes += sum(p.pool_bytes for p in self._pools)
        # columns of the one page table: the primary's, then each pool's
        self.table_width = self.max_pages + sum(
            p.layout.pages for p in self._pools)
        # -- slot allocator (page-table rows) --
        self._free = list(range(self.num_slots - 1, -1, -1))  # pop->0,1,..
        self._free_set = set(self._free)           # O(1) double-free check
        self._len = [0] * self.num_slots
        self._slot_blocks: List[List[int]] = [[] for _ in
                                              range(self.num_slots)]
        # host mirror of the device page table (scratch-filled rows)
        self.page_table = np.zeros((self.num_slots, self.table_width),
                                   np.int32)
        # -- block allocator (block 0 = scratch, never handed out) --
        self._free_blocks = list(range(self.num_blocks - 1, 0, -1))
        self._ref = [0] * self.num_blocks
        # -- hashed prefix cache --
        # digest -> block for EVERY registered block (whatever refcount);
        # _lru is the evictable subset (refcount 0), insertion order =
        # eviction order (oldest first; free(slot) re-inserts a retiring
        # sequence's deepest blocks first so shallow prefix blocks — the
        # likeliest future hits — are evicted last)
        self._by_hash: Dict[bytes, int] = {}
        self._hash_of: Dict[int, bytes] = {}
        self._lru: "OrderedDict[bytes, int]" = OrderedDict()
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.peak_blocks_used = 0
        # one-entry admission-plan memo: can_map() and the map_slot()
        # that immediately follows share one digest walk instead of
        # hashing the prompt twice; any allocator mutation invalidates
        self._plan_gen = 0
        self._plan_cache = None
        # deferred prefix-cache registration (chunked prefill):
        # slot -> [(block index in the page row, digest, block)] of
        # fresh full prompt blocks NOT yet published to the hash table —
        # a block only registers once the chunk dispatch that fills it
        # has been enqueued (register_prefix), so a concurrent
        # admission can never hash-hit unfilled rows. Dropped whole on
        # free(slot) (cancel/preempt mid-prefill).
        self._pending_reg: Dict[int, List[Tuple[int, bytes, int]]] = {}

    @property
    def cache_row_bytes(self) -> int:
        """Arena bytes one token holds in one layer, as stored (all
        heads; a quantized row's scale pair included)."""
        per_head = self.spec.row_width * self.dtype.itemsize \
            + (8 if self.kv_quantized else 0)
        return self.spec.heads * per_head

    # -- slot allocation ----------------------------------------------------

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def active_count(self) -> int:
        return self.num_slots - len(self._free)

    def alloc(self) -> Optional[int]:
        """Claim a free slot (page-table row); None when every row is
        occupied (the scheduler leaves the request queued). Pages are
        mapped separately by map_slot(). Host-swap resumes allocate
        through here too: the serving sampler is slot-independent
        (scheduler._sample_row), so a preempted sequence may resume in
        ANY free row bit-identically."""
        if not self._free:
            return None
        slot = self._free.pop()
        self._free_set.discard(slot)
        return slot

    def free(self, slot: int):
        """Release a slot: every mapped block is unreferenced (cached
        prefix blocks fall back to the LRU pool, private blocks to the
        free list) and the page row resets to scratch."""
        if not 0 <= slot < self.num_slots:
            raise ValueError(
                f"free() of slot {slot} out of range "
                f"[0, {self.num_slots})")
        if slot in self._free_set:
            raise ValueError(f"double free of slot {slot}")
        # deepest blocks decref'd (and LRU-inserted) first: shallow
        # prefix blocks land most-recently-used, evicted last
        # unpublished prefix digests die with the slot: their blocks'
        # fills may never have been dispatched (mid-prefill cancel)
        self._pending_reg.pop(slot, None)
        for b in reversed(self._slot_blocks[slot]):
            self._decref(b)
        self._slot_blocks[slot] = []
        for pool in self._pools:
            pool.release(slot)
        self.page_table[slot, :] = SCRATCH_BLOCK
        self._len[slot] = 0
        self._free.append(slot)
        self._free_set.add(slot)

    # -- block accounting ---------------------------------------------------

    @property
    def blocks_total(self) -> int:
        """Allocatable blocks (scratch excluded)."""
        return self.num_blocks - 1

    @property
    def blocks_used(self) -> int:
        """Blocks referenced by at least one live slot."""
        return self.blocks_total - len(self._free_blocks) - len(self._lru)

    @property
    def blocks_cached(self) -> int:
        """Unreferenced blocks kept warm for prefix-cache hits (LRU-
        evicted under pressure)."""
        return len(self._lru)

    @property
    def blocks_available(self) -> int:
        """Blocks an admission can claim right now: free + evictable."""
        return len(self._free_blocks) + len(self._lru)

    def blocks_for(self, positions: int) -> int:
        """Pages needed to hold `positions` sequence positions."""
        if positions < 1:
            raise ValueError(f"positions must be >= 1, got {positions}")
        return (positions - 1) // self.block_size + 1

    def _incref(self, block: int) -> None:
        self._plan_gen += 1
        self._ref[block] += 1
        if self._ref[block] == 1:
            digest = self._hash_of.get(block)
            if digest is not None:
                self._lru.pop(digest, None)     # no longer evictable

    def _decref(self, block: int) -> None:
        if self._ref[block] <= 0:
            raise ValueError(f"refcount underflow on block {block}")
        self._plan_gen += 1
        self._ref[block] -= 1
        if self._ref[block] == 0:
            digest = self._hash_of.get(block)
            if digest is not None:
                self._lru[digest] = block       # evictable, MRU end
            else:
                self._free_blocks.append(block)

    def _take_block(self) -> int:
        """Claim one block for exclusive use, evicting the oldest
        unreferenced cached block if the free list is empty."""
        self._plan_gen += 1
        if self._free_blocks:
            return self._free_blocks.pop()
        digest, block = self._lru.popitem(last=False)   # oldest
        del self._by_hash[digest]
        del self._hash_of[block]
        return block

    # -- hashed prefix cache ------------------------------------------------

    def _chain_digests(self, prompt: np.ndarray, n_full: int,
                       adapter_id: int = 0):
        """Chained per-block digests: digest[i] commits to the whole
        prefix tokens[0 : (i+1)*block_size], so a hit at block i implies
        hits at every block before it. The adapter id SALTS the chain
        seed: a prefix computed under LoRA adapter k holds different
        K/V content than the same tokens under the base model (or any
        other adapter), so cross-adapter sharing would be silent output
        corruption. adapter_id=0 seeds with the legacy empty chain, so
        an adapterless engine's digests — and its cross-request sharing
        — are byte-identical to pre-adapter builds."""
        bs = self.block_size
        data = np.ascontiguousarray(prompt[:n_full * bs], np.int32)
        digests, h = [], b""
        if adapter_id:
            h = np.int64(adapter_id).tobytes()
        for i in range(n_full):
            h = hashlib.blake2b(
                h + data[i * bs:(i + 1) * bs].tobytes(),
                digest_size=16).digest()
            digests.append(h)
        return digests

    def _plan(self, prompt: np.ndarray,
              total_positions: int, adapter_id: int = 0
              ) -> Tuple[list, List[int], int, int, bool]:
        """The admission plan, computed WITHOUT mutating anything:
        (digests of registerable full blocks, hit block ids, count of
        hits currently in the LRU pool, total blocks needed,
        feasible-right-now). LRU hits would be claimed, not evicted,
        so they are excluded from the evictable supply — and they are
        what blocks_needed() charges against availability. Memoized
        per (prompt, total) until the next allocator mutation — the
        can_map() check and the map_slot() that follows share one
        digest walk."""
        key = (prompt.tobytes(), int(total_positions), int(adapter_id))
        if self._plan_cache is not None:
            gen, k, plan = self._plan_cache
            if gen == self._plan_gen and k == key:
                return plan
        p_len = prompt.size
        total_blocks = self.blocks_for(total_positions)
        # shareable: full blocks strictly before position p_len-1 (the
        # suffix prefill always recomputes the last prompt position)
        shareable = (p_len - 1) // self.block_size
        digests = self._chain_digests(prompt, p_len // self.block_size,
                                      adapter_id) \
            if self.prefix_cache_enabled else []
        hit_blocks: List[int] = []
        lru_hits = 0
        for i in range(min(shareable, len(digests))):
            block = self._by_hash.get(digests[i])
            if block is None:
                break
            hit_blocks.append(block)
            if self._ref[block] == 0:
                lru_hits += 1
        feasible = (total_blocks - len(hit_blocks)
                    <= len(self._free_blocks) + len(self._lru)
                    - lru_hits
                    and all(pool.can_hold(total_positions)
                            for pool in self._pools))
        plan = (digests, hit_blocks, lru_hits, total_blocks, feasible)
        self._plan_cache = (self._plan_gen, key, plan)
        return plan

    def can_map(self, prompt: np.ndarray, total_positions: int,
                adapter_id: int = 0) -> bool:
        """Feasibility of map_slot() RIGHT NOW, without mutating any
        allocator state — the engine's pages-aware admission check
        (stamp/count a request as admitted only when it will fit)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        return self._plan(prompt, total_positions, adapter_id)[4]

    def blocks_needed(self, prompt: np.ndarray,
                      total_positions: int, adapter_id: int = 0) -> int:
        """Blocks a map_slot() of this request would actually CONSUME
        from blocks_available RIGHT NOW: fresh pages (total minus
        prefix-cache hits) PLUS the hit blocks currently sitting in
        the LRU pool — claiming those increfs them out of the
        evictable supply, so they cost availability exactly like a
        fresh page even though they cost no prefill. Hits on blocks a
        live sequence already references are genuinely free.
        Non-mutating (the planner's memoized digest walk). This is
        the number page reservations must use: reserving
        blocks_for(total) for a prompt whose prefix is shared with a
        RUNNING sequence over-reserves by the whole hit depth and can
        starve admission at a near-full arena."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        _, hit_blocks, lru_hits, total_blocks, _ = \
            self._plan(prompt, total_positions, adapter_id)
        return total_blocks - len(hit_blocks) + lru_hits

    def map_slot(self, slot: int, prompt: np.ndarray,
                 total_positions: int,
                 register: bool = True,
                 adapter_id: int = 0) -> Optional[Tuple[np.ndarray, int]]:
        """Map the pages a request needs into `slot`'s page row.

        prompt: the request's token ids; total_positions: p_len +
        max_new (every position the sequence may ever write). Leading
        FULL prompt blocks that hash-match cached ones are shared
        (refcounted) instead of allocated; the rest come from the free
        list, evicting LRU cached blocks under pressure. Returns
        (page_row (max_pages,) int32, prefix_len) — prefix_len is the
        number of leading positions already resident (a multiple of
        block_size; the prefill suffix starts there) — or None when the
        arena cannot hold the request right now (caller keeps it queued;
        the slot stays allocated and untouched).

        Sharing never includes the block holding position p_len-1: the
        suffix prefill always recomputes the last prompt position (its
        logits seed the first token), and the first block the request
        writes into is private by construction — the copy-on-write
        guarantee.

        `register=False` (chunked prefill) defers publishing this
        prompt's fresh full blocks to the prefix hash table: the caller
        releases them block by block via register_prefix() as the
        chunk dispatches that fill them are enqueued. Hits are still
        CONSUMED either way — deferral only gates what later
        admissions may share FROM this one."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        p_len = prompt.size
        if not 1 <= total_positions <= self.max_pages * self.block_size:
            raise ValueError(
                f"total_positions {total_positions} out of range "
                f"[1, {self.max_pages * self.block_size}]")
        if p_len > total_positions:
            raise ValueError(
                f"prompt ({p_len}) longer than total_positions "
                f"({total_positions})")
        bs = self.block_size
        digests, claimed, _lru_hits, total_blocks, feasible = \
            self._plan(prompt, total_positions, adapter_id)
        if not feasible:
            return None
        for b in claimed:
            self._incref(b)
        if self.prefix_cache_enabled:
            self.prefix_hits += len(claimed)
            self.prefix_misses += (p_len - 1) // bs - len(claimed)
        blocks = claimed + [self._take_block() for _ in
                            range(total_blocks - len(claimed))]
        for b in blocks[len(claimed):]:
            self._incref(b)
        # register this prompt's fresh FULL blocks so later admissions
        # can share them (content is deterministic in the prefix tokens;
        # the filling prefill dispatch is enqueued before any dispatch
        # that could read a future hit — with register=False the caller
        # upholds that invariant chunk by chunk via register_prefix).
        # A digest already registered to another block keeps its
        # original mapping.
        pending = [(i, digests[i], blocks[i])
                   for i in range(len(claimed), len(digests))]
        if register:
            for _, d, b in pending:
                if d not in self._by_hash:
                    self._by_hash[d] = b
                    self._hash_of[b] = d
        elif pending:
            self._pending_reg[slot] = pending
        row = self._install_blocks(slot, blocks, p_len)
        tracer = get_tracer()
        for pool in self._pools:
            # every further group's row, whole, beside the primary's
            if pool.layout.spec.state and tracer.enabled:
                # ring only: one an admission a state group
                args = {"slot": slot, "group": pool.layout.spec.name}
                with tracer.span("serving/state_alloc", "serving", args):
                    held = pool.claim(slot, total_positions)
                    args["block"] = held[0]
            else:
                held = pool.claim(slot, total_positions)
            row[pool.layout.start:pool.layout.start + len(held)] = held
        self.page_table[slot] = row
        return row, len(claimed) * bs

    def register_prefix(self, slot: int, frontier: int) -> None:
        """Publish `slot`'s deferred prefix digests for every full
        block now COVERED by the fill frontier (`frontier` = absolute
        positions whose filling dispatch is enqueued): block i
        registers once (i+1)*block_size <= frontier. The chunked-
        prefill caller invokes this right after each chunk dispatch,
        so device dispatch order guarantees a later hit's prefill
        reads filled rows. No-op for slots with nothing pending."""
        pending = self._pending_reg.get(slot)
        if not pending:
            return
        keep: List[Tuple[int, bytes, int]] = []
        for i, d, b in pending:
            if (i + 1) * self.block_size <= frontier:
                if d not in self._by_hash:
                    self._by_hash[d] = b
                    self._hash_of[b] = d
                    self._plan_gen += 1   # plans may now see the hit
            else:
                keep.append((i, d, b))
        if keep:
            self._pending_reg[slot] = keep
        else:
            self._pending_reg.pop(slot, None)

    def _install_blocks(self, slot: int, blocks, length: int):
        """Install already-claimed+increffed blocks into `slot`'s page
        row (scratch-padded) and update length/peak accounting — the
        shared tail of map_slot (admission) and adopt_blocks (swap-in)."""
        self._slot_blocks[slot] = blocks
        row = np.full((self.table_width,), SCRATCH_BLOCK, np.int32)
        row[:len(blocks)] = blocks
        self.page_table[slot] = row
        self._len[slot] = int(length)
        self.peak_blocks_used = max(self.peak_blocks_used,
                                    self.blocks_used)
        return row

    def mapped_block_count(self, slot: int) -> int:
        """Blocks currently mapped into `slot`'s page row — what a
        host-swap of this slot must copy out and later re-adopt."""
        return len(self._slot_blocks[slot])

    # -- host-swap adoption -------------------------------------------------

    def can_adopt(self, n_blocks: int) -> bool:
        """Feasibility of adopt_blocks() RIGHT NOW: the arena can supply
        `n_blocks` private blocks (free + LRU-evictable)."""
        if n_blocks < 1:
            raise ValueError(f"n_blocks must be >= 1, got {n_blocks}")
        if self._pools:
            raise ValueError(
                "a swapped sequence carries the primary cache group alone: "
                "a model with several groups is not swapped or migrated")
        return n_blocks <= self.blocks_available

    def adopt_blocks(self, slot: int, n_blocks: int,
                     length: int) -> np.ndarray:
        """Claim `n_blocks` PRIVATE blocks for a swapped-in sequence and
        install them in `slot`'s page row (length = live positions).

        Unlike map_slot() this never consults or feeds the prefix
        cache: the blocks' contents are about to be restored from the
        host swap pool, and a swapped-in prefix re-registering its
        hashes would race the admission that may have re-registered the
        same digests while the sequence was out. Returns the page row
        ((max_pages,) int32, scratch-padded) to scatter the payload
        through; caller must have checked can_adopt()."""
        if self._slot_blocks[slot]:
            raise ValueError(f"slot {slot} already has mapped blocks")
        if not self.can_adopt(n_blocks):
            raise ValueError(
                f"arena cannot supply {n_blocks} blocks "
                f"({self.blocks_available} available)")
        blocks = [self._take_block() for _ in range(n_blocks)]
        for b in blocks:
            self._incref(b)
        return self._install_blocks(slot, blocks, length)

    # -- per-slot length tracking ------------------------------------------

    def set_length(self, slot: int, n: int):
        if not 0 <= n <= self.max_len:
            raise ValueError(
                f"slot length {n} out of range [0, {self.max_len}]")
        self._len[slot] = int(n)

    def advance(self, slot: int):
        self.set_length(slot, self._len[slot] + 1)

    def length(self, slot: int) -> int:
        return self._len[slot]

    # -- arena threading ----------------------------------------------------

    @property
    def arena(self):
        """What the scheduler's jitted entry points thread and donate:
        the bare data array for a full-precision pool, the (data,
        scale plane) pytree for an int8 pool — the form the paged
        kernels' _arena_parts expects. Same donation discipline either
        way (a tuple donates both leaves)."""
        if self.kv_scales is not None:
            return (self.kv, self.kv_scales)
        if self._pools:
            # one arena a cache group, the primary first
            return (self.kv,) + tuple(p.kv for p in self._pools)
        return self.kv

    def store_arena(self, arena) -> None:
        """Store a dispatch's arena output back (the donated buffers'
        successors) — the write half of the `arena` property."""
        if self._pools:
            self.kv = arena[0]
            for pool, kv in zip(self._pools, arena[1:]):
                pool.kv = kv
        elif self.kv_scales is not None:
            self.kv, self.kv_scales = arena
        else:
            self.kv = arena

    @property
    def kv_dtype(self) -> str:
        """The arena's storage dtype name ("float32" / "bfloat16" /
        "int8") — the string occupancy(), /varz, and /healthz report;
        migration tickets are dtype-checked against the numpy dtype
        behind it."""
        return str(self.dtype)

    @property
    def pool_bytes(self) -> int:
        """WHOLE-ARENA HBM footprint — constant for the engine's life
        (donation reuses the same buffer in place every dispatch),
        derived from the ACTUAL storage itemsize plus the scale plane
        on a quantized pool. On a tensor-parallel mesh this is the sum
        across chips; the number one chip actually holds is
        hbm_per_chip_bytes."""
        return self._pool_bytes

    @property
    def mesh_shape(self) -> Tuple[int, ...]:
        """The arena's mesh geometry, (tp,) — (1,) on a single chip."""
        return (self.mesh_shards,)

    @property
    def hbm_per_chip_bytes(self) -> int:
        """Arena bytes RESIDENT PER CHIP: the heads axis shards over
        the tp mesh, so each chip holds pool_bytes / tp (exact —
        divisibility is enforced at construction). This is the number
        capacity planning must use on a sharded pool; pool_bytes alone
        overstates per-chip HBM by the mesh factor."""
        return self._pool_bytes // self.mesh_shards

    def request_shortfall(self, positions: int) -> Optional[str]:
        """Why a request of `positions` positions could NEVER be mapped,
        whatever is free (a pool smaller than the request), or None."""
        if self.blocks_for(positions) > self.blocks_total:
            return (f"request needs {self.blocks_for(positions)} KV blocks "
                    f"but the arena only has {self.blocks_total}")
        for pool in self._pools:
            if pool.blocks_for(positions) > pool.blocks_total:
                return (f"request needs {pool.blocks_for(positions)} blocks "
                        f"of the cache group {pool.layout.spec.name!r} but "
                        f"its pool only has {pool.blocks_total}")
        return None

    def group_rows(self, slot: int) -> Dict[str, int]:
        """{group name: rows the group can hold of `slot` right now}: its
        mapped blocks' worth (a window group's never passes window +
        block_size)."""
        rows = {self.spec.name:
                len(self._slot_blocks[slot]) * self.block_size}
        rows.update({p.layout.spec.name: p.held_rows(slot)
                     for p in self._pools if not p.layout.spec.state})
        return rows

    def state_occupancy(self) -> Optional[Dict[str, object]]:
        """The STATE groups' blocks, summed over the groups (each holds
        one a slot): total, in use, the peak, and the bytes a slot holds
        of them in all; None for a model without one."""
        pools = [p for p in self._pools if p.layout.spec.state]
        if not pools:
            return None
        return {"groups": [p.layout.spec.name for p in pools],
                "blocks_total": sum(p.blocks_total for p in pools),
                "blocks_used": sum(p.blocks_used for p in pools),
                "peak_blocks_used": sum(p.peak_blocks_used for p in pools),
                "bytes_a_slot": sum(p.block_bytes for p in pools)}

    def occupancy(self) -> Dict[str, object]:
        out = self._occupancy()
        if self._pools:
            # every pool, the primary first (whose numbers are also the
            # top-level ones); `pool_bytes` above is their sum
            spec = self.spec
            out["groups"] = [
                {"name": spec.name, "window": spec.window,
                 "layers": spec.layers, "pages_a_slot": self.max_pages,
                 "blocks_total": self.blocks_total,
                 "blocks_used": self.blocks_used,
                 "peak_blocks_used": self.peak_blocks_used,
                 "pool_bytes": self._pool_bytes
                 - sum(p.pool_bytes for p in self._pools)}
            ] + [p.occupancy() for p in self._pools]
            out["prefix_cache"] = (
                "off: a hit is valid only with the slot's state at its "
                "edge, and no snapshot is kept"
                if any(p.layout.spec.state for p in self._pools) else
                "off: a window group's rows before a hit are not kept")
        return out

    def _occupancy(self) -> Dict[str, object]:
        return {"num_slots": self.num_slots,
                "active_slots": self.active_count,
                "free_slots": self.free_count,
                "live_positions": sum(self._len),
                "pool_bytes": self.pool_bytes,
                "hbm_per_chip_bytes": self.hbm_per_chip_bytes,
                "kv_dtype": self.kv_dtype,
                "mesh_shape": self.mesh_shape,
                "block_size": self.block_size,
                "blocks_total": self.blocks_total,
                "blocks_used": self.blocks_used,
                "blocks_cached": self.blocks_cached,
                "peak_blocks_used": self.peak_blocks_used,
                "prefix_hits": self.prefix_hits,
                "prefix_misses": self.prefix_misses}
