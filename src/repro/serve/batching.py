"""Request-level batching state: queue, slots, and a paged KV cache.

The pieces the continuous-batching server composes:

  * :class:`Request` / :class:`RequestQueue` - arrival-time-ordered intake.
  * :class:`Slot` - one occupied batch lane: position, pending input token,
    output buffer, timing marks (TTFT / per-token latency).
  * :class:`PagedKVCache` - a block pool with a free list. KV for every
    slot lives in fixed-size blocks indexed by a per-slot block table, so a
    mixed-length batch holds exactly the blocks its sequences need instead
    of ``n_slots * max_len`` of padding, and blocks freed by a finished
    request are immediately reusable by the next admission. This is the
    serving-side analogue of the macro free-list the MARS allocator manages:
    storage is granted at a fixed quantum and recycled wave by wave.

Physical block 0 is reserved as scratch: idle batch lanes read and write it
so every decode step keeps a fixed shape, and its contents are never
attended by a live slot.

Block lifecycle contract (load-bearing for prefix sharing, see
``serve.prefix``):

  * every non-scratch block carries a REFCOUNT. ``_alloc`` grants a block
    at refcount 1; ``retain``/``release`` move it; a block whose refcount
    hits 0 is SCRUBBED (zeroed, or NaN-poisoned under ``debug_poison``)
    and returned to the LIFO free list - a reused block can never leak the
    previous request's K/V into the next slot's gathered view.
  * the same physical block may appear in several slot tables (and in the
    prefix trie) - that is what a prefix-cache hit adopts. Accounting
    (``blocks_in_use``, ``peak_blocks``, the ``kv_utilization`` gauge)
    counts PHYSICAL live blocks, so shared blocks are never double-counted:
    ``free_blocks + blocks_in_use == n_blocks - 1`` always.
  * every write path (``write_prefill`` / ``write_token`` / ``write_run``)
    is copy-on-write: a write landing in a block with refcount > 1 first
    copies the block (ALL tiers - the tiers share one refcount ledger) into
    a fresh allocation and repoints only the writer's table entry.
  * ``ensure`` is all-or-nothing: on pool exhaustion it raises WITHOUT
    growing the table, so a caller that catches the error and requeues the
    request leaks nothing.
"""
from __future__ import annotations

import dataclasses
import functools
import heapq
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kernels.cim_bsr_matmul import MACRO_AXIS
from ..models.config import ModelConfig


def kv_view_spec(cfg: ModelConfig, mesh: Mesh) -> Optional[P]:
    """PartitionSpec for the gathered paged-KV views (L, B, Sv, KV, dh):
    heads over the ``macro`` axis when the KV-head count divides it, else
    None (serve replicated - correctness first). The single source of truth
    for whether macro serving shards KV."""
    if MACRO_AXIS not in mesh.axis_names:
        return None
    n_dev = int(mesh.shape[MACRO_AXIS])
    if n_dev > 1 and cfg.n_kv_heads_eff % n_dev == 0:
        return P(None, None, None, MACRO_AXIS, None)
    return None


# -- the pool's device programs ----------------------------------------------
#
# Every read and write of the pool is one jitted program. Block ids, offsets,
# the tier and the scrub value are traced operands, never static ones, so
# each program compiles once per operand shape: the gather once per view
# bucket, ``write_prefill`` once per padded prompt length, a decode step's
# write, the copy-on-write copy and the scrub once each.


def _take_view(pool, tbl, tier):
    """(L, B, n_view*bs, KV, dh) view of ``pool[tier]`` over the block
    table ``tbl`` (B, n_view)."""
    g = pool[tier, tbl]  # (B, n_view, L, bs, KV, dh)
    b, n_view, n_layers, bs, n_kv, dh = g.shape
    return g.transpose(2, 0, 1, 3, 4, 5).reshape(n_layers, b, n_view * bs,
                                                 n_kv, dh)


def _put_rows(pool_k, pool_v, tier, pb, off, k, v):
    """Entry j of ``k``/``v`` (L, n, KV, dh) into position ``off[j]`` of
    block ``pb[j]`` of ``pool[tier]``."""
    def put(pool, x):
        return pool.at[tier, pb, :, off].set(
            jnp.swapaxes(x, 0, 1).astype(pool.dtype))
    return put(pool_k, k), put(pool_v, v)


def _put_blocks(pool_k, pool_v, tier, ids, k, v):
    """``k``/``v`` (L, S, KV, dh), cut into blocks, into blocks ``ids`` of
    ``pool[tier]``; the last block is zero-padded past S."""
    def put(pool, x):
        n_layers, s, n_kv, dh = x.shape
        n, bs = ids.shape[0], pool.shape[3]
        x = jnp.pad(x, ((0, 0), (0, n * bs - s), (0, 0), (0, 0)))
        blocks = x.reshape(n_layers, n, bs, n_kv, dh).swapaxes(0, 1)
        return pool.at[tier, ids].set(blocks.astype(pool.dtype))
    return put(pool_k, k), put(pool_v, v)


def _copy_block(pool_k, pool_v, src, dst):
    return (pool_k.at[:, dst].set(pool_k[:, src]),
            pool_v.at[:, dst].set(pool_v[:, src]))


def _fill_block(pool_k, pool_v, block, fill):
    return (pool_k.at[:, block].set(fill.astype(pool_k.dtype)),
            pool_v.at[:, block].set(fill.astype(pool_v.dtype)))


class _PoolPrograms(NamedTuple):
    take_view: object
    put_rows: object
    put_blocks: object
    copy_block: object
    fill_block: object


@functools.lru_cache(maxsize=None)
def _pool_programs(donate: bool, pool_sharding: Optional[NamedSharding],
                   view_sharding: Optional[NamedSharding]) -> _PoolPrograms:
    """The pool's programs for one placement, shared by every cache built
    with it (a server builds a cache per ``run``), so a second run finds
    each program compiled."""
    view = {} if view_sharding is None else {"out_shardings": view_sharding}
    write = {"donate_argnums": (0, 1) if donate else ()}
    if pool_sharding is not None:
        write["out_shardings"] = (pool_sharding, pool_sharding)
    return _PoolPrograms(
        take_view=jax.jit(_take_view, **view),
        put_rows=jax.jit(_put_rows, **write),
        put_blocks=jax.jit(_put_blocks, **write),
        copy_block=jax.jit(_copy_block, **write),
        fill_block=jax.jit(_fill_block, **write))


@dataclasses.dataclass
class Request:
    """One generation request. ``arrival`` is seconds relative to the start
    of the serve loop (0 = already waiting).

    ``priority`` orders READY requests (higher admits first; equal
    priorities keep strict FIFO). ``deadline`` is an absolute trace-clock
    second past which serving the request is pointless (the gateway sheds
    it instead of admitting); ``tenant`` attributes the request to a
    gateway tenant ("" = single-tenant serving)."""

    rid: str
    prompt: np.ndarray  # (S,) int32 token ids
    max_new_tokens: int
    arrival: float = 0.0
    priority: int = 0
    deadline: Optional[float] = None
    tenant: str = ""

    def __post_init__(self):
        self.prompt = np.asarray(self.prompt, np.int32).reshape(-1)
        if self.prompt.size == 0:
            raise ValueError(f"{self.rid}: empty prompt")
        if self.max_new_tokens < 1:
            raise ValueError(f"{self.rid}: max_new_tokens must be >= 1")
        if self.deadline is not None and self.deadline < self.arrival:
            raise ValueError(f"{self.rid}: deadline {self.deadline} before "
                             f"arrival {self.arrival}")


class RequestQueue:
    """Two-stage intake: a time heap for not-yet-arrived requests and a
    priority heap for ready ones.

    ``pop_ready(now)`` first promotes every request whose arrival has
    passed, then pops the highest-priority ready request; equal priorities
    break ties by admission order (stable FIFO - the seed behavior when
    every priority is 0). ``requeue`` returns a popped-but-unadmitted
    request to the FRONT of its priority class so smaller peers can never
    leapfrog it forever.

    ``max_pending`` bounds the TOTAL queued count: an overflowing push
    evicts the lowest-priority / newest request (possibly the incoming one)
    and RETURNS it instead of silently dropping, incrementing ``n_shed`` -
    the gateway mirrors that into its ``gateway_shed_total`` counter."""

    def __init__(self, requests: Optional[List[Request]] = None,
                 max_pending: Optional[int] = None):
        self._arrivals: list = []  # (arrival, seq, req)
        self._ready: list = []     # (-priority, seq, req)
        self._seq = 0
        self._front = -1
        self.max_pending = max_pending
        self.n_shed = 0
        for r in requests or []:
            self.push(r)

    def push(self, req: Request) -> Optional[Request]:
        """Queue a request; returns the request SHED by an overflowing
        push (None when everything fits)."""
        shed = None
        if self.max_pending is not None and len(self) >= self.max_pending:
            shed = self._evict_for(req)
            if shed is req:
                self.n_shed += 1
                return shed
        heapq.heappush(self._arrivals, (req.arrival, self._seq, req))
        self._seq += 1
        if shed is not None:
            self.n_shed += 1
        return shed

    def _evict_for(self, incoming: Request) -> Request:
        """Pick the overflow victim: lowest priority first, newest within a
        priority class (front-of-cohort requeues carry negative seq and are
        therefore the oldest, i.e. the most protected)."""
        victim_key, victim = (incoming.priority, -self._seq), incoming
        for heap in (self._arrivals, self._ready):
            for _, seq, req in heap:
                key = (req.priority, -seq)
                if key < victim_key:
                    victim_key, victim = key, req
        if victim is not incoming:
            for heap in (self._arrivals, self._ready):
                for i, entry in enumerate(heap):
                    if entry[2] is victim:
                        heap[i] = heap[-1]
                        heap.pop()
                        heapq.heapify(heap)
                        return victim
        return victim

    def requeue(self, req: Request) -> None:
        """Return a popped-but-unadmitted request to the FRONT of its
        priority class (a plain push would hand it a fresh sequence number
        and let smaller same-arrival peers leapfrog it forever)."""
        heapq.heappush(self._ready, (-req.priority, self._front, req))
        self._front -= 1

    def _promote(self, now: float) -> None:
        while self._arrivals and self._arrivals[0][0] <= now:
            _, seq, req = heapq.heappop(self._arrivals)
            heapq.heappush(self._ready, (-req.priority, seq, req))

    def pop_ready(self, now: float) -> Optional[Request]:
        self._promote(now)
        if self._ready:
            return heapq.heappop(self._ready)[2]
        return None

    def next_arrival(self) -> Optional[float]:
        """Earliest instant at which SOME request is (or was) ready."""
        vals = []
        if self._arrivals:
            vals.append(self._arrivals[0][0])
        if self._ready:
            vals.append(min(t[2].arrival for t in self._ready))
        return min(vals) if vals else None

    def __len__(self) -> int:
        return len(self._arrivals) + len(self._ready)


@dataclasses.dataclass
class Slot:
    """Per-lane decode state while a request occupies a batch slot."""

    req: Request
    pos: int  # next KV write position == current sequence length
    next_token: int  # pending input token (last sampled)
    out: List[int]
    t_admit: float
    token_times: List[float]
    queue_wait_s: float = 0.0  # admission minus arrival (TTFT's queue share)
    prefix_tokens: int = 0  # prompt tokens adopted from the prefix cache

    @property
    def done(self) -> bool:
        return len(self.out) >= self.req.max_new_tokens

    @property
    def worst_positions(self) -> int:
        """KV positions this request can ever occupy (for reservation)."""
        return len(self.req.prompt) + self.req.max_new_tokens


class PagedKVCache:
    """Block-pooled KV storage for the dense/moe/vlm attention cache.

    pool_k / pool_v: (tiers, n_blocks, L, block_size, KV, dh) device
    arrays. Per-slot block tables (host lists) map logical block i ->
    physical block id. ``gather`` produces, on the device, the contiguous
    (L, B, Sv, KV, dh) view a decode step attends over - sized by the
    deepest ACTIVE slot, not by the engine's max length.

    ``tiers`` > 1 keeps SEVERAL KV pools behind ONE block layout: every
    tier shares the block tables, free list and accounting, so positions
    line up exactly across tiers. This is how speculative serving keeps a
    draft-tier cache next to the target-tier cache without duplicating any
    allocation state (tier 0 = target, tier 1 = draft).
    """

    def __init__(self, cfg: ModelConfig, n_slots: int, n_blocks: int,
                 block_size: int, dtype=None, mesh: Optional[Mesh] = None,
                 tiers: int = 1, debug_poison: bool = False):
        if n_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is scratch)")
        if tiers < 1:
            raise ValueError("need >= 1 KV tier")
        self.cfg = cfg
        self.n_slots = n_slots
        self.n_blocks = n_blocks
        self.block_size = block_size
        self.tiers = tiers
        # macro-cluster serving: the pool and its gathered views are
        # sharded heads-wise over the mesh when KV heads divide it, so each
        # device holds and attends only its resident heads (1/N of every
        # block); otherwise both are replicated over the mesh
        self.mesh = mesh
        spec = None if mesh is None else kv_view_spec(cfg, mesh)
        self._view_sharding = (None if spec is None
                               else NamedSharding(mesh, spec))
        self._pool_sharding = self._table_sharding = None
        if mesh is not None:
            self._table_sharding = NamedSharding(mesh, P())
            self._pool_sharding = (self._table_sharding if spec is None
                                   else NamedSharding(mesh, P(None, *spec)))
        shape = (tiers, n_blocks, cfg.n_layers, block_size,
                 cfg.n_kv_heads_eff, cfg.dh)
        self.pool_k = jnp.zeros(shape, dtype or cfg.param_dtype,
                                device=self._pool_sharding)
        self.pool_v = jnp.zeros(shape, dtype or cfg.param_dtype,
                                device=self._pool_sharding)
        # every write is a functional update of the whole pool: donating
        # the pool lets it land in place, so a write moves only the rows it
        # touches. Donation is gated as the server gates it (CPU XLA
        # only warns), and each write rebinds pool_k / pool_v to the result.
        self._ops = _pool_programs(
            jax.default_backend() != "cpu", self._pool_sharding,
            self._view_sharding if spec is not None
            else self._table_sharding)
        # LIFO free list => a freed block is the first one re-granted
        self._free: List[int] = list(range(1, n_blocks))
        self.tables: List[List[int]] = [[] for _ in range(n_slots)]
        # per-block refcount: 0 = free (or scratch), 1 = exclusively owned,
        # >1 = shared (appears in several tables and/or the prefix trie)
        self.refcnt = np.zeros(n_blocks, np.int32)
        # scrub freed blocks with NaN instead of 0 (float pools only): a
        # live gather that wrongly references a freed block then poisons
        # its attention output instead of silently reading zeros
        self.debug_poison = debug_poison
        # stats
        self._ever_used: set = set()
        self.n_alloc = 0
        self.n_reused = 0
        self.n_cow = 0
        self.peak_blocks = 0
        self.h2d_bytes = 0  # block-table bytes gathers placed on the device

    # -- accounting ---------------------------------------------------------

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def blocks_in_use(self) -> int:
        """PHYSICAL live blocks (refcount > 0): a block shared by several
        tables and/or the prefix trie counts once, never per reference."""
        return int((self.refcnt[1:] > 0).sum())

    def blocks_for(self, n_pos: int) -> int:
        return -(-n_pos // self.block_size)

    def stats(self) -> dict:
        return {
            "n_blocks": self.n_blocks,
            "block_size": self.block_size,
            "kv_tiers": self.tiers,
            "allocations": self.n_alloc,
            "reused_blocks": self.n_reused,
            "cow_copies": self.n_cow,
            "peak_blocks": self.peak_blocks,
            "h2d_bytes": self.h2d_bytes,
            "kv_heads_sharded": self._view_sharding is not None,
        }

    # -- allocation ---------------------------------------------------------

    def _alloc(self) -> int:
        if not self._free:
            raise RuntimeError(
                "paged KV pool exhausted - admission control should have "
                "reserved worst-case blocks; raise n_blocks")
        b = self._free.pop()
        if b in self._ever_used:
            self.n_reused += 1
        self._ever_used.add(b)
        self.n_alloc += 1
        self.refcnt[b] = 1
        self.peak_blocks = max(self.peak_blocks, self.blocks_in_use)
        return b

    def retain(self, block: int) -> None:
        """Add a reference to a LIVE block (sharing it into another table
        or the prefix trie)."""
        if block <= 0 or block >= self.n_blocks or self.refcnt[block] < 1:
            raise ValueError(f"retain: block {block} is not a live block")
        self.refcnt[block] += 1

    def release(self, block: int) -> None:
        """Drop one reference; the last release scrubs the block and
        returns it to the LIFO free list."""
        if block <= 0 or block >= self.n_blocks or self.refcnt[block] < 1:
            raise ValueError(f"release: block {block} is not a live block")
        self.refcnt[block] -= 1
        if self.refcnt[block] == 0:
            self._scrub(block)
            self._free.append(block)

    def _scrub(self, block: int) -> None:
        fill = (np.nan if self.debug_poison
                and jnp.issubdtype(self.pool_k.dtype, jnp.floating) else 0.0)
        self.pool_k, self.pool_v = self._ops.fill_block(
            self.pool_k, self.pool_v, block, np.float32(fill))

    def adopt(self, slot: int, blocks: List[int]) -> None:
        """Append already-live shared blocks to ``slot``'s table (a
        prefix-cache hit adopting a matched chain), retaining each. Must
        precede any ``ensure`` growth so logical positions line up."""
        t = self.tables[slot]
        for b in blocks:
            self.retain(b)
            t.append(b)

    def ensure(self, slot: int, n_pos: int) -> None:
        """Grow ``slot``'s table until positions [0, n_pos) fit.

        All-or-nothing: if the pool cannot cover the WHOLE growth the call
        raises without appending anything, so a caller that catches the
        exhaustion and requeues the request leaks no blocks."""
        t = self.tables[slot]
        need = self.blocks_for(n_pos) - len(t)
        if need > len(self._free):
            raise RuntimeError(
                "paged KV pool exhausted - admission control should have "
                "reserved worst-case blocks; raise n_blocks")
        for _ in range(need):
            t.append(self._alloc())

    def free_slot(self, slot: int) -> None:
        # reversed so the slot's FIRST block lands last on the LIFO free
        # list and is therefore the first one re-granted (blocks shared
        # with other tables/the trie stay live - only this reference drops)
        for b in reversed(self.tables[slot]):
            self.release(b)
        self.tables[slot] = []

    def _ensure_owned(self, slot: int, block_idx: int) -> int:
        """Copy-on-write: make ``slot``'s logical block ``block_idx``
        exclusively owned before a write. Shared blocks are copied (every
        tier - the tiers share one refcount ledger) into a fresh
        allocation and only the writer's table entry is repointed."""
        pb = self.tables[slot][block_idx]
        if self.refcnt[pb] == 1:
            return pb
        nb = self._alloc()  # raises on exhaustion BEFORE any state moves
        self.pool_k, self.pool_v = self._ops.copy_block(
            self.pool_k, self.pool_v, pb, nb)
        self.tables[slot][block_idx] = nb
        self.release(pb)
        self.n_cow += 1
        return nb

    # -- data movement ------------------------------------------------------

    def _local(self, x):
        """A K/V operand where the pool's programs can take it. A single-
        device pool is uncommitted (so are its views, as the decode
        program's warm-up views are): an operand committed to a device
        (a gateway's dedicated prefill device) comes by the host instead of
        committing the pool. On a mesh, so does one committed elsewhere."""
        if not getattr(x, "committed", False) or (
                self._pool_sharding is not None and x.sharding.device_set
                == self._pool_sharding.device_set):
            return x
        return np.asarray(x)

    def write_prefill(self, slot: int, k: jnp.ndarray, v: jnp.ndarray,
                      true_len: int, tier: int = 0, start: int = 0) -> None:
        """Scatter a prefill cache (L, S_pad, KV, dh) into ``slot``'s blocks
        covering positions ``start .. start+true_len-1`` (``start`` must be
        block-aligned - the suffix-prefill path after a prefix-cache hit).
        Only the covered blocks are allocated; pad positions inside the last
        block carry garbage that decode overwrites before its mask ever
        reaches them."""
        bs = self.block_size
        if start % bs:
            raise ValueError(f"write_prefill start={start} must be a "
                             f"multiple of block_size={bs}")
        self.ensure(slot, start + true_len)
        b0 = start // bs
        # one id per block of the padded prefill; blocks past true_len
        # write the scratch block
        ids = np.zeros(self.blocks_for(k.shape[1]), np.int32)
        for i in range(self.blocks_for(true_len)):
            ids[i] = self._ensure_owned(slot, b0 + i)
        self.pool_k, self.pool_v = self._ops.put_blocks(
            self.pool_k, self.pool_v, tier, ids, self._local(k),
            self._local(v))

    def view_tables(self, n_view: int,
                    slots: Optional[List[int]] = None) -> np.ndarray:
        """(len(slots), n_view) physical ids (all slots by default);
        short/idle slots pad with the scratch block (masked out by per-row
        positions)."""
        sl = list(range(self.n_slots)) if slots is None else slots
        tbl = np.zeros((len(sl), n_view), np.int32)
        for r, s in enumerate(sl):
            t = self.tables[s]
            n = min(len(t), n_view)
            tbl[r, :n] = t[:n]
        return tbl

    def place(self, tbl: np.ndarray) -> jnp.ndarray:
        """The placement stage of ``gather``: copy a block table (from
        ``view_tables``) to the device, replicated on a macro mesh. The
        copy may still be in flight when this returns."""
        self.h2d_bytes += tbl.nbytes
        if self._table_sharding is not None:
            return jax.device_put(tbl, self._table_sharding)
        return jnp.asarray(tbl)

    def gather_view(self, tbl: jnp.ndarray, tensor: str,
                    tier: int = 0) -> jnp.ndarray:
        """The gather stage of ``gather``: one tensor's ("k" or "v")
        (L, B, n_view*block_size, KV, dh) view over the placed block table
        ``tbl``, taken on the device (sharded heads-wise on a macro mesh
        whose devices divide the KV heads)."""
        pool = self.pool_k if tensor == "k" else self.pool_v
        return self._ops.take_view(pool, tbl, tier)

    def gather(self, n_view: int, tier: int = 0,
               slots: Optional[List[int]] = None
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(L, B, n_view*block_size, KV, dh) contiguous K/V views on the
        device; ``slots`` restricts B to those lanes (a cache-hit suffix
        pass gathers ONE). One block table is placed for both."""
        tbl = self.place(self.view_tables(n_view, slots))
        return self.gather_view(tbl, "k", tier), \
            self.gather_view(tbl, "v", tier)

    def write_coords(self, positions: List[Optional[int]]
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """Physical (block, offset) per lane for a decode-step write; idle
        lanes (None) target the scratch block. Copy-on-write fires here:
        the coords returned always point at exclusively-owned blocks."""
        pb = np.zeros((self.n_slots,), np.int32)
        off = np.zeros((self.n_slots,), np.int32)
        for s, pos in enumerate(positions):
            if pos is None:
                continue
            pb[s] = self._ensure_owned(s, pos // self.block_size)
            off[s] = pos % self.block_size
        return pb, off

    def write_token(self, pb: np.ndarray, off: np.ndarray,
                    k_new: jnp.ndarray, v_new: jnp.ndarray,
                    tier: int = 0) -> None:
        """Write one decode step's K/V (L, B, KV, dh) into the pool at the
        (block, offset) of each lane (from ``write_coords``)."""
        self.pool_k, self.pool_v = self._ops.put_rows(
            self.pool_k, self.pool_v, tier, pb, off, self._local(k_new),
            self._local(v_new))

    def write_run(self, slot: int, start: int, k_run: np.ndarray,
                  v_run: np.ndarray, tier: int = 0) -> None:
        """Commit a variable-length run of K/V entries (L, T, KV, dh) for
        ONE slot at positions ``start .. start+T-1``.

        This is the speculative accept path: the verify/draft passes
        compute k+1 candidate entries but only the accepted prefix is ever
        passed here - rejected draft KV is rolled back by simply never
        reaching the pool (the gathered views the rejects were written
        into are throwaways)."""
        bs = self.block_size
        n = k_run.shape[1]
        if n == 0:
            return
        for bi in range(start // bs, (start + n - 1) // bs + 1):
            self._ensure_owned(slot, bi)
        t = self.tables[slot]
        at = np.arange(start, start + n)
        pb = np.asarray([t[p // bs] for p in at], np.int32)
        self.write_token(pb, (at % bs).astype(np.int32), k_run, v_run,
                         tier=tier)
