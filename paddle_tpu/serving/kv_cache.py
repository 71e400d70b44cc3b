"""Blocked (paged) KV-cache pool for the generation serving runtime
(vLLM SOSP '23 PagedAttention, mapped onto the framework's fixed-shape
decode step) — with content-addressed **radix prefix caching** (SGLang
RadixAttention mapped onto flat block tables).

The device side is one dense array per PART of a token's cache entry,
``[n_layers, num_blocks + 1, block_size, *part_shape]``, which the
jitted steps take as donated arguments and return updated, so the pool
never round-trips over the host link. What one token's entry is the
MODEL says (:class:`CacheEntry`, ``GenerationModel.cache_entry()``): a
per-head cache states two parts ``k`` and ``v`` of ``[n_heads,
head_dim]`` (the default, float32), a latent cache one part of
``[width]`` shared by every head and no ``v``. The block accounting
below is the same whatever the entry. A
sequence's cache is NOT contiguous: it owns an ordered list of block ids
(its *block table*). The paged kernels take ``k``/``v`` whole and find
``(layer, block_table[b, j])`` in their index map; no step slices a
layer out of the pool in front of a kernel (XLA would copy the layer's
pages: docs/SERVING.md, "No kernel step slices the pool"). The lax path
gathers ``k[layer][block_table]`` to reconstruct the sequence's logical
``[max_seq_len]`` key/value layout. Fixed shapes everywhere means XLA
compiles the step exactly once no matter how sequences join and retire.

Block 0 is the *null block*: it is never allocated, every unused
block-table entry points at it, and inactive batch slots route their
(masked-out) cache writes into it — so scatter/gather indices are always
in range without per-slot branches in the compiled step.

Allocation is host-side and two-phase:

  * ``reserve(n)`` at admission: the scheduler reserves the worst-case
    block count for a request (``ceil((prompt + max_new) / block_size)``)
    before it joins the batch. Admission control — a request only enters
    the batch when its whole reservation fits, so the pool can never be
    exhausted mid-decode and no preemption/swap path is needed.
  * ``alloc_block(owner)`` per crossing: physical ids are handed out
    lazily as the sequence's position crosses a block boundary, drawn
    from the reservation made at admit time.

``free_owner`` returns a retired sequence's blocks and releases any
unused remainder of its reservation. ``truncate_owner`` is the
speculative-decoding **rollback** path (docs/SERVING.md): rejected
draft positions wrote KV into over-allocated tail blocks, and
truncation hands them back while growing the owner's reservation by
the same count — the exact inverse of ``alloc_block``, so the
two-phase invariant survives rewinds.

Prefix caching (docs/SERVING.md) makes the pool *content-addressed*:

  * Every block is refcounted. A FULL block whose contents are a known
    prompt span can be *sealed* into the content index under a
    chain-hash key (:func:`prefix_chain_keys`: key ``i`` commits to the
    namespace — the model — plus every token of blocks ``0..i``, so
    equal keys imply an identical prompt prefix AND an identical chain
    of predecessor blocks).
  * ``reserve(owner, n, prefix_keys=...)`` adopts the longest sealed
    run of the caller's prefix keys: matched blocks join the new
    owner's table with a refcount bump, and only the remainder of the
    worst case is actually reserved — the admission gate shrinks by
    exactly the shared span.
  * A shared block is returned to circulation only when its refcount
    hits zero; sealed blocks then park on an LRU *cached* list instead
    of the free list, still indexed, so a later identical prefix can
    revive them without recomputation. ``alloc_block`` evicts from the
    LRU (dropping the index entry) only once the free list is empty.

Pages of more than one KIND (docs/SERVING.md, "Two kinds of page"). A
model whose layers do not all keep the same positions states its kinds
(:class:`PageKind`): a ``window`` kind keeps only the last ``window``
positions of a sequence, the others keep all. Each kind has device
arrays of its own (its layers, its block count) and a free list of its
own, under the ONE lock, owner set, ``reserve`` / ``alloc_block`` /
``free_owner`` / ``truncate_owner`` / ``check_invariants`` / ``stats``
of this pool: a reservation covers every kind or none, and
``release_head`` hands a window kind's pages back as a sequence's
positions slide out of the window (the owner's reservation of that kind
grows back by as many, the inverse of ``alloc_block`` from the other
end). A block table of a window kind is indexed by the logical page
(``position // block_size``) like any other; released entries point at
the null block. Kinds past the first are plain: no sharing, no content
index (the engine refuses the prefix cache with them). A pool of one
kind is the pool described above, attribute for attribute.

A ROW STATE beside the pages (:class:`RowState`; docs/SERVING.md, "A
fourth block", "A fifth block"): a block whose token needs something of
its predecessors that no page holds states arrays a batch row, one a
named part with its own dtype (a convolution's last inputs; the matrix
a linear-attention scan carries, which is LARGER than the row's pages);
the pool carries them through every step with its own arrays
(``step_arrays``), audits them in ``check_invariants`` and reports their
bytes beside the pages' in ``stats()``. It has no accounting (a slot's
entry is whoever sits there's; position 0 ignores it): the batch rows
bound it as the blocks bound the pages, and admission needs both a free
row and its pages. That is why the engine refuses the prefix cache and
speculation with it.

Reservation conservation survives sharing (pinned by test):
``blocks_free(+cached) - reserved >= 0`` at every point, and
``free + cached + owned + shared == total`` — reviving a cached block
during adoption is charged against availability exactly like an
allocation, so outstanding reservations can never be left unbacked
(the two-phase no-deadlock invariant).
"""

import hashlib
from collections import OrderedDict

import numpy as np

__all__ = ["CacheEntry", "KVBlockPool", "PageKind", "RowState",
           "row_state_parts", "blocks_needed", "prefix_chain_keys"]


class CacheEntry:
    """What a model caches for one token in one layer: named parts, each
    with the shape of one token's values, and the dtype they are stored
    in. ``CacheEntry.per_head(H, Dh)`` is the K and V of a multi-head
    block; a latent block states ``CacheEntry((("latent", (width,)),),
    "bfloat16")``."""

    __slots__ = ("parts", "dtype")

    def __init__(self, parts, dtype="float32"):
        self.parts = tuple((str(n), tuple(int(d) for d in shape))
                           for n, shape in parts)
        if not self.parts:
            raise ValueError("a cache entry needs at least one part")
        self.dtype = str(dtype)

    @classmethod
    def per_head(cls, n_heads, head_dim, dtype="float32"):
        return cls((("k", (n_heads, head_dim)), ("v", (n_heads, head_dim))),
                   dtype)

    def __repr__(self):
        return "CacheEntry(%r, %r)" % (self.parts, self.dtype)


class PageKind:
    """One kind of page: its name, the layers (indices into the model's
    layers) whose cache lives in it, and ``window``: how many positions
    back a position of those layers sees, itself included (``None``:
    all of them, so nothing is ever released)."""

    __slots__ = ("name", "layers", "window")

    def __init__(self, name, layers, window=None):
        self.name = str(name)
        self.layers = tuple(int(i) for i in layers)
        self.window = None if window is None else int(window)
        if not self.layers:
            raise ValueError("page kind %r holds no layer" % self.name)

    def first_live_page(self, position, block_size):
        """The first logical page a query at ``position`` still reads."""
        if self.window is None:
            return 0
        return max(int(position) - self.window + 1, 0) // int(block_size)

    def __repr__(self):
        return "PageKind(%r, %r, %r)" % (self.name, self.layers,
                                         self.window)


def row_state_parts(shape, dtype="float32"):
    """:class:`RowState`'s arguments after ``max_batch`` (what a block's
    ``row_state(config)`` returns) as ``(name, shape, dtype)`` parts: a
    sequence of such parts as it is, one shape and dtype as the one part
    ``state``."""
    named = bool(shape) and not isinstance(shape[0], int)
    return tuple(shape) if named else (("state", tuple(shape), dtype),)


class RowState:
    """A second kind of state beside the pages: arrays a BATCH ROW
    (``[max_batch] + shape`` each), for a block whose token needs
    something of its predecessors that no page holds (a convolution's
    last inputs, a shifted value, the matrix a linear-attention scan
    carries over the whole sequence). It has no accounting: a row's
    entry belongs to whatever sequence sits in the slot, every step
    rewrites the entries of the rows it computed, and a step ignores the
    entry of a row whose first token is at position 0, so admission and
    retirement leave it alone; what bounds it is the number of batch
    rows, as blocks bound the pages. The steps take the arrays after the
    pool's, donated, and return them (``KVBlockPool.step_arrays``).

    ``RowState(max_batch, shape, dtype)`` is one array (``shape``,
    ``dtype``, ``array``); ``RowState(max_batch, parts)`` with ``parts``
    a sequence of ``(name, shape, dtype)`` is one array a NAMED PART,
    each in its own dtype, as :class:`CacheEntry` names the parts of a
    page (``parts``, ``arrays``, ``part(name)``): a scan's float32
    matrices beside a convolution's narrower inputs.

    What is NOT kept, and why the engine refuses the features that would
    need it: the state at a page boundary of an adopted prefix (the
    prefix cache), and the state before a window that is rolled back
    (speculation)."""

    __slots__ = ("max_batch", "parts", "arrays")

    def __init__(self, max_batch, shape, dtype="float32"):
        import jax.numpy as jnp

        self.max_batch = int(max_batch)
        self.parts = tuple((str(n), tuple(int(d) for d in s), jnp.dtype(t))
                           for n, s, t in row_state_parts(shape, dtype))
        if len({n for n, _s, _t in self.parts}) != len(self.parts):
            raise ValueError("row state parts need distinct names: %r"
                             % (self.parts,))
        self.arrays = tuple(jnp.zeros((self.max_batch,) + s, t)
                            for _n, s, t in self.parts)

    # -- a row state of one part, as first written ------------------------
    def _only(self):
        if len(self.parts) != 1:
            raise AttributeError(
                "this row state has %d parts (%s): ask for one by name"
                % (len(self.parts), ", ".join(n for n, _s, _t in self.parts)))
        return self.parts[0]

    shape = property(lambda self: self._only()[1])
    dtype = property(lambda self: self._only()[2])

    @property
    def array(self):
        self._only()
        return self.arrays[0]

    @array.setter
    def array(self, value):
        self._only()
        self.arrays = (value,)

    def part(self, name):
        """The array of the part called ``name``."""
        for (n, _s, _t), a in zip(self.parts, self.arrays):
            if n == name:
                return a
        raise KeyError("row state has no part %r" % (name,))

    def part_bytes(self):
        """{part: bytes it holds on the device, all rows}."""
        return {n: self.max_batch * int(np.prod(s, dtype=np.int64))
                * t.itemsize for n, s, t in self.parts}

    @property
    def nbytes(self):
        return sum(self.part_bytes().values())

    def check_invariants(self):
        """Problem strings (empty: clean): each array a step handed back
        is the one stated, and still there (a donated array that no
        step's result replaced is deleted)."""
        problems = []
        if len(self.arrays) != len(self.parts):
            return ["row state: %d arrays for %d parts"
                    % (len(self.arrays), len(self.parts))]
        for (n, s, t), a in zip(self.parts, self.arrays):
            want = (self.max_batch,) + s
            what = "row state" if len(self.parts) == 1 \
                else "row state part %r" % n
            if a.shape != want or a.dtype != t:
                problems.append("%s: %s %s where %s %s was stated" % (
                    what, a.dtype, a.shape, t, want))
            elif a.is_deleted():
                problems.append("%s: the array was donated to a step and "
                                "not replaced by its result" % what)
        return problems

    def __repr__(self):
        if len(self.parts) == 1:
            return "RowState(%d, %r, %r)" % (self.max_batch, self.shape,
                                             str(self.dtype))
        return "RowState(%d, %r)" % (self.max_batch, tuple(
            (n, s, str(t)) for n, s, t in self.parts))


class _KindPages:
    """The accounting of one kind of page past the first: a free list,
    each owner's reservation and table. ``owned[owner]`` holds the LIVE
    block ids in table order; ``head[owner]`` counts the logical pages
    released in front of them, so entry ``i`` is logical page
    ``head + i``. Mutated under the pool's lock only."""

    def __init__(self, kind, num_blocks):
        if num_blocks < 1:
            raise ValueError("page kind %r needs at least one usable "
                             "block" % kind.name)
        self.kind = kind
        self.num_blocks = int(num_blocks)
        self.free = list(range(self.num_blocks, 0, -1))
        self.reserved = {}
        self.owned = {}
        self.head = {}
        self.ceiling = {}
        self.released = 0        # cumulative, by release_head

    def available(self):
        return len(self.free) - sum(self.reserved.values())

    def in_use(self):
        return self.num_blocks - len(self.free)


def blocks_needed(num_tokens, block_size):
    """Blocks required to hold ``num_tokens`` cache slots."""
    if num_tokens <= 0:
        return 0
    return -(-int(num_tokens) // int(block_size))


def prefix_chain_keys(token_ids, block_size, namespace=""):
    """Content-addressed keys for every FULL block of ``token_ids``.

    ``key[i]`` is a hash chain committing to ``namespace`` (the model),
    ``key[i-1]`` and block ``i``'s token content — two requests share
    ``key[i]`` iff their first ``(i + 1) * block_size`` tokens are
    identical under the same namespace. Returns
    ``len(token_ids) // block_size`` hex digests (the trailing partial
    block, whose content a future decode would extend, is never keyed).
    """
    bs = int(block_size)
    h = hashlib.sha1(("ptpu-prefix:%s" % namespace).encode()).hexdigest()
    out = []
    for i in range(len(token_ids) // bs):
        blk = token_ids[i * bs:(i + 1) * bs]
        h = hashlib.sha1(
            (h + ":" + ",".join(str(int(t)) for t in blk)).encode()
        ).hexdigest()
        out.append(h)
    return out


class KVBlockPool:
    """Fixed-size-block KV cache pool with refcounted per-owner block
    accounting and an optional content-addressed prefix index.

    ``num_blocks`` counts usable blocks; one extra null block (id 0) is
    added on top, so the device arrays hold ``num_blocks + 1`` blocks.
    """

    NULL_BLOCK = 0

    def __init__(self, n_layers, n_heads, head_dim, block_size,
                 num_blocks, dtype=None, device=None, entry=None,
                 kinds=None, row_state=None):
        """``entry`` is the model's :class:`CacheEntry`; without one the
        pool holds K and V ``[n_heads, head_dim]`` a token. ``dtype``,
        when given, overrides the entry's. ``kinds`` (a sequence of
        :class:`PageKind`, the model's) splits the layers over kinds of
        page; ``num_blocks`` is then ``{kind name: usable blocks}`` or a
        sequence in the kinds' order. ``row_state`` (a
        :class:`RowState`, or None) rides with the pool's arrays
        through every step."""
        kinds = tuple(kinds) if kinds else (
            PageKind("all", range(int(n_layers))),)
        if isinstance(num_blocks, dict):
            num_blocks = [num_blocks[k.name] for k in kinds]
            if len(kinds) == 1:
                num_blocks, = num_blocks
        if len(kinds) > 1:
            num_blocks = [int(n) for n in num_blocks]
            if len(num_blocks) != len(kinds):
                raise ValueError("one block count a page kind: %r for %r"
                                 % (num_blocks, kinds))
            if sorted(i for k in kinds for i in k.layers) \
                    != list(range(int(n_layers))):
                raise ValueError("page kinds %r do not partition %d "
                                 "layers" % (kinds, n_layers))
            if kinds[0].window is not None:
                raise ValueError("the first page kind keeps every "
                                 "position (window None)")
            extra = [_KindPages(k, n)
                     for k, n in zip(kinds[1:], num_blocks[1:])]
            num_blocks = num_blocks[0]
        else:
            extra = []
        self.kinds = kinds
        self._extra = extra
        self.row_state = row_state
        if num_blocks < 1:
            raise ValueError("KVBlockPool needs at least one usable block")
        if block_size < 1:
            raise ValueError("block_size must be >= 1")
        self.n_layers = int(n_layers)
        self.n_heads = int(n_heads)
        self.head_dim = int(head_dim)
        self.block_size = int(block_size)
        self.num_blocks = int(num_blocks)
        if entry is None:
            entry = CacheEntry.per_head(self.n_heads, self.head_dim)
        self.entry = entry

        import contextlib

        import jax
        import jax.numpy as jnp

        # via jnp so that bfloat16 (no numpy type of its own) is a name
        self.dtype = jnp.dtype(dtype if dtype is not None else entry.dtype)
        with (jax.default_device(device) if device is not None
              else contextlib.nullcontext()):
            # one device array per part, in the entry's order (kind by
            # kind where there are several); the steps take and return
            # them as a tuple
            self.arrays = tuple(
                jnp.zeros((len(kind.layers), n + 1, self.block_size)
                          + shape, self.dtype)
                for kind, n in zip(kinds, [self.num_blocks]
                                   + [x.num_blocks for x in extra])
                for _name, shape in entry.parts)

        from ..analysis.concurrency import make_lock

        self._lock = make_lock("serving.kv_pool")
        # LIFO free list: a retired sequence's blocks are handed to the
        # next admit while still warm in cache
        self._free = list(range(self.num_blocks, 0, -1))
        self._reserved = {}      # owner -> blocks still reservable
        self._owned = {}         # owner -> [block ids], table order
        # owner -> reserved + owned ceiling, fixed at reserve() time:
        # alloc_block moves one unit reserved->owned, truncate_owner
        # moves it back, so the sum is invariant until free_owner —
        # check_invariants pins it (the rollback accounting audit)
        self._reserve_ceiling = {}
        # -- content-addressed prefix state -----------------------------
        self._refs = {}          # bid -> refcount (>= 1 while in a table)
        self._sealed = {}        # content key -> bid
        self._block_key = {}     # bid -> content key (sealed blocks)
        # refcount-0 sealed blocks, oldest-freed first (the LRU evictees)
        self._cached = OrderedDict()   # bid -> content key
        # cumulative rollback accounting (speculative decoding's
        # truncate path — surfaced in stats() so the drafter-pool and
        # target-pool rollback volume is auditable per pool)
        self.truncate_calls = 0
        self.blocks_truncated = 0

    # -- the two parts of a per-head entry, by name ----------------------
    def _part(self, name):
        for i, (n, _shape) in enumerate(self.entry.parts):
            if n == name:
                return i
        raise AttributeError(
            "this pool's cache entry %r has no part %r"
            % (self.entry, name))

    def _set_part(self, name, value):
        i = self._part(name)
        self.arrays = self.arrays[:i] + (value,) + self.arrays[i + 1:]

    @property
    def step_arrays(self):
        """What a step takes after the weights and hands back first:
        the pages' arrays, then the row state's, one a part, where there
        is one."""
        if self.row_state is None:
            return self.arrays
        return self.arrays + self.row_state.arrays

    @step_arrays.setter
    def step_arrays(self, value):
        value = tuple(value)
        if self.row_state is not None:
            n = len(self.row_state.parts)
            self.row_state.arrays = value[-n:]
            value = value[:-n]
        self.arrays = value

    k = property(lambda self: self.arrays[self._part("k")],
                 lambda self, v: self._set_part("k", v))
    v = property(lambda self: self.arrays[self._part("v")],
                 lambda self, v: self._set_part("v", v))

    # -- accounting ----------------------------------------------------
    @property
    def blocks_total(self):
        return self.num_blocks

    @property
    def blocks_free(self):
        """Blocks reclaimable for a new reservation: truly free plus
        refcount-zero cached prefix blocks, minus what reservations
        already spoke for."""
        with self._lock:
            return (len(self._free) + len(self._cached)
                    - sum(self._reserved.values()))

    @property
    def blocks_in_use(self):
        """Unique blocks referenced by at least one owner's table."""
        with self._lock:
            return len(self._refs)

    @property
    def blocks_cached(self):
        """Refcount-zero sealed blocks kept for prefix reuse."""
        with self._lock:
            return len(self._cached)

    def stats(self):
        with self._lock:
            free = len(self._free)
            cached = len(self._cached)
            reserved = sum(self._reserved.values())
            owned = sum(1 for r in self._refs.values() if r == 1)
            shared = len(self._refs) - owned
            by_kind = {x.kind.name: {"blocks_total": x.num_blocks,
                                     "blocks_in_use": x.in_use(),
                                     "blocks_reserved":
                                         sum(x.reserved.values()),
                                     "blocks_released": x.released}
                       for x in self._extra}
        out = {
            "blocks_total": self.num_blocks,
            "blocks_in_use": owned + shared,
            "blocks_owned": owned,
            "blocks_shared": shared,
            "blocks_cached": cached,
            "blocks_reserved": reserved,
            "blocks_free": free + cached - reserved,
            "utilization": (owned + shared) / self.num_blocks,
            "truncate_calls": self.truncate_calls,
            "blocks_truncated": self.blocks_truncated,
        }
        if by_kind:
            # the figures above are the first kind's; every kind's own
            # are under its name, the first among them
            by_kind = {self.kinds[0].name: {
                "blocks_total": self.num_blocks,
                "blocks_in_use": owned + shared,
                "blocks_reserved": reserved, "blocks_released": 0},
                **by_kind}
            out["kinds"] = by_kind
            out["window_blocks_released"] = sum(
                k["blocks_released"] for k in by_kind.values())
        # the two kinds of state side by side: what the pages hold (all
        # kinds, the null blocks too) and what the batch rows carry
        out["page_bytes"] = sum(int(a.size) * a.dtype.itemsize
                                for a in self.arrays)
        if self.row_state is not None:
            out["row_state_bytes"] = self.row_state.nbytes
            out["row_state_parts"] = self.row_state.part_bytes()
        return out

    # -- admission-side API --------------------------------------------
    def _per_kind(self, n):
        """``n`` as one count a kind (an int: the first kind's)."""
        if not hasattr(n, "__len__"):
            n = (int(n),) + (0,) * len(self._extra)
        n = tuple(int(c) for c in n)
        if len(n) != len(self.kinds):
            raise ValueError("%d counts for %d page kinds"
                             % (len(n), len(self.kinds)))
        return n

    def kind_totals(self):
        """Usable blocks, a count a page kind."""
        return (self.num_blocks,) + tuple(x.num_blocks
                                          for x in self._extra)

    def could_hold(self, n):
        """Whether an EMPTY pool could cover the reservation ``n``."""
        return all(c <= total for c, total in
                   zip(self._per_kind(n), self.kind_totals()))

    def can_reserve(self, n):
        n = self._per_kind(n)
        with self._lock:
            return all(x.available() >= c
                       for x, c in zip(self._extra, n[1:])) and (
                len(self._free) + len(self._cached)
                - sum(self._reserved.values()) >= n[0])

    def reserve(self, owner, n, prefix_keys=None):
        """Reserve ``n`` worst-case blocks for ``owner``. Returns False
        (reserving nothing) when the pool cannot cover the reservation —
        the scheduler's admission check.

        With ``prefix_keys`` (the prompt's :func:`prefix_chain_keys`),
        the longest sealed run is adopted first: matched blocks join the
        owner's table (``block_table(owner)``) with a refcount bump and
        only ``n - matched`` blocks are actually reserved. Reviving a
        refcount-zero cached block is charged against availability like
        an allocation, so reservations already outstanding stay backed.

        With several page kinds ``n`` is one count a kind, and every
        kind's count is reserved or none is.
        """
        n, *n_extra = self._per_kind(n)
        with self._lock:
            if owner in self._reserved or owner in self._owned:
                raise ValueError("owner %r already holds a reservation"
                                 % (owner,))
            matched = []
            if prefix_keys:
                for key in prefix_keys:
                    bid = self._sealed.get(key)
                    if bid is None:
                        break
                    matched.append(bid)
            revive = sum(1 for bid in matched
                         if self._refs.get(bid, 0) == 0)
            need = max(n - len(matched), 0)
            avail = (len(self._free) + len(self._cached)
                     - sum(self._reserved.values()))
            if avail < need + revive or any(
                    x.available() < c
                    for x, c in zip(self._extra, n_extra)):
                return False
            for x, c in zip(self._extra, n_extra):
                x.reserved[owner] = x.ceiling[owner] = c
                x.owned[owner] = []
                x.head[owner] = 0
            for bid in matched:
                r = self._refs.get(bid, 0)
                if r == 0:
                    self._cached.pop(bid, None)  # revive from the LRU
                self._refs[bid] = r + 1
            self._reserved[owner] = need
            self._owned[owner] = list(matched)
            self._reserve_ceiling[owner] = need + len(matched)
            return True

    def alloc_block(self, owner, kind=0):
        """Hand one physical block id to ``owner``, drawn from its
        reservation (appends to the owner's block table). Evicts the
        least-recently-freed cached prefix block when the free list is
        empty (its content-index entry is dropped). ``kind`` indexes
        ``self.kinds``; each kind's ids are its own arrays' pages."""
        with self._lock:
            if kind:
                x = self._extra[kind - 1]
                if x.reserved.get(owner, 0) <= 0:
                    raise RuntimeError(
                        "owner %r has no remaining reservation of %s "
                        "pages (release_head comes before alloc_block)"
                        % (owner, x.kind.name))
                bid = x.free.pop()
                x.reserved[owner] -= 1
                x.owned[owner].append(bid)
                return bid
            if self._reserved.get(owner, 0) <= 0:
                raise RuntimeError(
                    "owner %r has no remaining reservation — the "
                    "scheduler must reserve the worst-case block count "
                    "at admission" % (owner,))
            if self._free:
                bid = self._free.pop()
            else:
                bid, key = self._cached.popitem(last=False)
                del self._sealed[key]
                del self._block_key[bid]
            self._reserved[owner] -= 1
            self._refs[bid] = 1
            self._owned[owner].append(bid)
            return bid

    def block_table(self, owner, kind=0):
        """The owner's block ids in table order; of a window kind the
        LIVE ones (``pages_released`` logical pages lie before them)."""
        with self._lock:
            if kind:
                return list(self._extra[kind - 1].owned.get(owner, ()))
            return list(self._owned.get(owner, ()))

    def pages_released(self, owner, kind):
        with self._lock:
            return self._extra[kind - 1].head.get(owner, 0) if kind else 0

    def release_head(self, owner, kind, first_live):
        """Hand back ``owner``'s pages of a window kind whose logical
        page number is below ``first_live``: the positions they hold
        have slid out of the window of every query still to come. The
        owner's reservation of the kind grows back by as many (the
        inverse of ``alloc_block``), so a row's live pages plus its
        reservation never exceed what ``reserve`` gave it. Returns the
        released block ids, oldest first."""
        if not kind or self.kinds[kind].window is None:
            raise ValueError("page kind %r keeps every position"
                             % self.kinds[kind].name)
        with self._lock:
            x = self._extra[kind - 1]
            blocks = x.owned.get(owner)
            if blocks is None:
                raise KeyError("owner %r holds no block table" % (owner,))
            n = min(max(int(first_live) - x.head[owner], 0), len(blocks))
            dropped = blocks[:n]
            del blocks[:n]
            x.head[owner] += n
            x.free.extend(reversed(dropped))
            x.reserved[owner] += n
            x.released += n
            return dropped

    def free_owner(self, owner):
        """Drop ``owner``'s references and release the unused part of
        its reservation. A block returns to circulation only at
        refcount zero: sealed blocks park on the cached LRU (still
        prefix-matchable), unsealed ones go back to the free list.
        Parking walks the table in REVERSE order so eviction consumes a
        chain tail-first — the longest-prefix-match walks head-first,
        so evicting the head would strand every still-cached successor
        as unmatchable dead index entries. Idempotent. Returns the
        number of blocks the owner's table held."""
        with self._lock:
            for x in self._extra:
                x.free.extend(reversed(x.owned.pop(owner, [])))
                for d in (x.reserved, x.head, x.ceiling):
                    d.pop(owner, None)
            blocks = self._owned.pop(owner, [])
            self._reserved.pop(owner, None)
            self._reserve_ceiling.pop(owner, None)
            for bid in reversed(blocks):
                r = self._refs.get(bid, 0) - 1
                if r > 0:
                    self._refs[bid] = r
                    continue
                self._refs.pop(bid, None)
                key = self._block_key.get(bid)
                if key is not None:
                    self._cached[bid] = key
                    self._cached.move_to_end(bid)
                else:
                    self._free.append(bid)
            return len(blocks)

    def truncate_owner(self, owner, n_keep):
        """Rewind ``owner``'s block table to its first ``n_keep``
        entries — the KV **rollback** path of speculative decoding
        (docs/SERVING.md): positions written for rejected draft tokens
        live in over-allocated tail blocks, and this returns them.

        Each dropped block leaves the table, clears its refcount, and
        goes back to the free list while the owner's RESERVATION grows
        back by one — the exact inverse of ``alloc_block``, so the
        two-phase no-deadlock invariant is preserved and the rewound
        sequence re-crosses the same block boundaries without needing
        a new reservation. Only unshared (refcount 1), unsealed tail
        blocks may be truncated; a sealed or adopted prefix block can
        never sit past a rollback point (the scheduler only rewinds
        decode-phase positions), so hitting one raises rather than
        corrupting the content index. Returns the dropped block ids in
        table order."""
        n_keep = int(n_keep)
        if n_keep < 0:
            raise ValueError("n_keep must be >= 0, got %d" % n_keep)
        with self._lock:
            blocks = self._owned.get(owner)
            if blocks is None:
                raise KeyError("owner %r holds no block table" % (owner,))
            for x in self._extra:
                # the same logical pages, of every kind
                tail = x.owned[owner]
                keep = max(n_keep - x.head[owner], 0)
                x.free.extend(reversed(tail[keep:]))
                x.reserved[owner] += len(tail[keep:])
                del tail[keep:]
            if n_keep >= len(blocks):
                return []
            dropped = blocks[n_keep:]
            for bid in dropped:
                if self._refs.get(bid, 0) != 1:
                    raise RuntimeError(
                        "refusing to truncate block %d with refcount %d "
                        "— shared blocks are never rolled back"
                        % (bid, self._refs.get(bid, 0)))
                if bid in self._block_key:
                    raise RuntimeError(
                        "refusing to truncate sealed block %d (key %s..)"
                        " — cached prefix blocks are never rolled back"
                        % (bid, self._block_key[bid][:8]))
            del blocks[n_keep:]
            # reversed: the shallowest dropped block lands last on the
            # LIFO free list, so re-crossing the same boundary hands
            # the SAME (cache-warm) block back first
            for bid in reversed(dropped):
                del self._refs[bid]
                self._free.append(bid)
            self._reserved[owner] = (self._reserved.get(owner, 0)
                                     + len(dropped))
            self.truncate_calls += 1
            self.blocks_truncated += len(dropped)
            return list(dropped)

    # -- runtime invariants (docs/STATIC_ANALYSIS.md, PTPU_LOCK_CHECK) -
    def check_invariants(self):
        """Audit the pool's accounting in one consistent snapshot and
        return a list of problem strings (empty = clean). The serving
        engine calls this at step boundaries under ``PTPU_LOCK_CHECK=1``
        and reports findings as ``pool-invariant`` violations; the pins:

          * conservation: ``free + cached + in-table == total`` (the
            ``free+reserved+owned+shared==total`` identity of stats(),
            with reservations counted against availability)
          * every referenced block has refcount >= 1, reservations are
            never negative, and outstanding reservations stay backed
            (``free + cached - reserved >= 0`` — the two-phase
            no-deadlock invariant)
          * LRU/index consistency: sealed index and reverse map agree,
            cached blocks are exactly the refcount-zero sealed ones,
            the null block never circulates, and no block id appears
            twice across free/cached/tables
          * rollback accounting (speculative decoding's truncate path):
            every owner's ``reserved + owned`` still equals the ceiling
            fixed at ``reserve()`` time (``alloc_block`` moves a unit
            one way, ``truncate_owner`` moves it back), and no
            free-list block retains a content-index entry (a truncated
            or flushed block must leave the index)
          * the row state, where the pool carries one
            (:meth:`RowState.check_invariants`)
        """
        problems = ([] if self.row_state is None
                    else self.row_state.check_invariants())
        with self._lock:
            for x in self._extra:
                problems.extend(self._check_kind(x))
            free = list(self._free)
            cached = list(self._cached)
            refs = dict(self._refs)
            reserved = dict(self._reserved)
            owned = {o: list(b) for o, b in self._owned.items()}
            sealed = dict(self._sealed)
            block_key = dict(self._block_key)
            ceilings = dict(self._reserve_ceiling)
        n_free, n_cached, n_tab = len(free), len(cached), len(refs)
        if n_free + n_cached + n_tab != self.num_blocks:
            problems.append(
                "conservation broken: free %d + cached %d + in-table %d "
                "!= total %d" % (n_free, n_cached, n_tab,
                                 self.num_blocks))
        for bid, r in refs.items():
            if r < 1:
                problems.append("block %d referenced with refcount %d"
                                % (bid, r))
        for owner, n in reserved.items():
            if n < 0:
                problems.append("owner %r reservation went negative (%d)"
                                % (owner, n))
        n_reserved = sum(max(n, 0) for n in reserved.values())
        if n_free + n_cached < n_reserved:
            problems.append(
                "reservations unbacked: free %d + cached %d < reserved "
                "%d" % (n_free, n_cached, n_reserved))
        for key, bid in sealed.items():
            if block_key.get(bid) != key:
                problems.append(
                    "sealed index maps key %s.. to block %d but the "
                    "block's key is %r" % (key[:8], bid,
                                           block_key.get(bid)))
        for bid, key in block_key.items():
            if sealed.get(key) != bid:
                problems.append(
                    "block %d keyed %s.. missing from the sealed index"
                    % (bid, key[:8]))
        for bid in cached:
            if bid in refs:
                problems.append("cached block %d is also referenced "
                                "(refcount %d)" % (bid, refs[bid]))
            if bid not in block_key:
                problems.append("cached block %d lost its index entry"
                                % bid)
        seen = {}
        for where, ids in (("free", free), ("cached", cached)):
            for bid in ids:
                if bid == self.NULL_BLOCK:
                    problems.append("null block circulating on the %s "
                                    "list" % where)
                if bid in seen:
                    problems.append("block %d on both %s and %s"
                                    % (bid, seen[bid], where))
                seen[bid] = where
        for owner, blocks in owned.items():
            for bid in blocks:
                if refs.get(bid, 0) < 1:
                    problems.append(
                        "owner %r table references block %d with no "
                        "refcount" % (owner, bid))
                if bid in seen:
                    problems.append("block %d in a table but also on "
                                    "the %s list" % (bid, seen[bid]))
        # rollback accounting: reserve()'s ceiling is conserved across
        # alloc_block/truncate_owner round trips
        for owner, blocks in owned.items():
            ceiling = ceilings.get(owner)
            have = reserved.get(owner, 0) + len(blocks)
            if ceiling is None:
                problems.append("owner %r holds a table but no "
                                "reservation ceiling" % (owner,))
            elif have != ceiling:
                problems.append(
                    "owner %r reserved %d + owned %d != reservation "
                    "ceiling %d (truncate/alloc accounting drift)"
                    % (owner, reserved.get(owner, 0), len(blocks),
                       ceiling))
        for bid in free:
            if bid in block_key:
                problems.append(
                    "free-list block %d still carries content-index "
                    "key %s.. (truncated/flushed blocks must leave "
                    "the index)" % (bid, block_key[bid][:8]))
        return problems

    @staticmethod
    def _check_kind(x):
        """A plain kind's audit (under the lock): conservation, backed
        reservations, ``reserved + owned == ceiling`` an owner, no block
        twice."""
        name, problems = x.kind.name, []
        held = [b for blocks in x.owned.values() for b in blocks]
        if len(x.free) + len(held) != x.num_blocks:
            problems.append(
                "%s pages: conservation broken: free %d + in-table %d != "
                "total %d" % (name, len(x.free), len(held), x.num_blocks))
        ids = x.free + held
        if len(set(ids)) != len(ids) or KVBlockPool.NULL_BLOCK in ids:
            problems.append("%s pages: a block twice, or the null block, "
                            "across the free list and the tables" % name)
        if x.available() < 0:
            problems.append(
                "%s pages: reservations unbacked: free %d < reserved %d"
                % (name, len(x.free), sum(x.reserved.values())))
        for owner, blocks in x.owned.items():
            have = x.reserved.get(owner, 0) + len(blocks)
            if x.reserved.get(owner, 0) < 0 \
                    or have != x.ceiling.get(owner):
                problems.append(
                    "%s pages: owner %r reserved %d + owned %d != "
                    "ceiling %r" % (name, owner,
                                    x.reserved.get(owner, 0), len(blocks),
                                    x.ceiling.get(owner)))
        return problems

    # -- content index (radix prefix caching) --------------------------
    def seal_block(self, bid, key):
        """Register a FULL, fully-written prompt block in the content
        index so later ``reserve(prefix_keys=...)`` calls can adopt it.
        Only live (refcount >= 1) non-null blocks are sealable; the
        first sealer of a key wins (a concurrent identical prefill just
        keeps its private copy). Returns True when ``bid`` is the
        canonical block for ``key``."""
        bid = int(bid)
        with self._lock:
            if bid == self.NULL_BLOCK or self._refs.get(bid, 0) < 1:
                return False
            if bid in self._block_key:
                return self._block_key[bid] == key
            if key in self._sealed:
                return False
            self._sealed[key] = bid
            self._block_key[bid] = key
            return True

    def lookup_prefix(self, prefix_keys):
        """Longest sealed run of ``prefix_keys`` currently adoptable
        (diagnostic; admission uses the atomic ``reserve``)."""
        with self._lock:
            out = []
            for key in prefix_keys:
                bid = self._sealed.get(key)
                if bid is None:
                    break
                out.append(bid)
            return out

    def flush_prefix_cache(self):
        """Drop the whole content index (after a weight hot-swap —
        cached KV state is only valid for the weights that computed it;
        ``ServingEngine.swap_weights`` calls this in the same critical
        section that installs the new weights, so a stale prefix can
        never serve a post-swap request). Referenced blocks stay in
        their owners' tables but lose their index entry; cached blocks
        return to the free list. Returns the number of index entries
        dropped."""
        from ..observability import metrics as _metrics

        with self._lock:
            dropped = len(self._sealed)
            self._free.extend(self._cached)
            self._cached.clear()
            self._sealed.clear()
            self._block_key.clear()
        _metrics.counter("serving/prefix_cache_flushes").inc()
        return dropped
