"""Pair materialization + deduplication (paper §3.1 "Pair Deduplication").

This is the *output* stage — the paper materializes pairs once, after all
iterations, because it is the single most expensive data-movement step
(68B pairs on the 530M-row run). The enumeration + "largest block wins"
cross-block dedupe therefore runs on device through the
``repro.kernels.pairs`` engine, with this module as a thin host driver:

- block reconstruction (group accepted (rid, key) assignments by key)
  into the CSR ``Blocks`` form,
- backend selection: ``backend="numpy"`` is the host reference
  implementation (the original shift-method enumeration + lexsort
  dedupe); ``"jax"`` decodes pair slots with fused XLA integer ops;
  ``"pallas"`` routes the triangular decode through the Pallas TPU kernel
  (interpreted on the CPU backend only). ``"auto"`` picks ``"jax"`` when
  the int32 device contract holds (all rids < 2**31, block sizes <=
  ``kernels.pairs.MAX_BLOCK_N``, budget < 2**31) and falls back to numpy
  with a warning otherwise; ``"distributed"`` dispatches to the
  fingerprint-routed shard-local dedupe over a device mesh
  (``core.distributed.dedupe_pairs_distributed``).
- chunking contract: device backends enumerate the canonical pair-slot
  space (blocks in CSR order, row-major triangle within a block — see
  ``kernels/pairs/ref.py``) in fixed-shape chunks of ``chunk_pairs``
  slots, so compilation is amortized across chunks and datasets and
  device memory stays bounded by ``budget + chunk_pairs`` pair slots
  regardless of corpus size. The final dedupe is ONE device sort by
  (a, b, size-descending) + a segment-start winner mask — no host hash
  pass.
- pair-budget guard: beyond ``budget`` total slots the engine switches to
  exact *counting* plus uniform slot *sampling* (``sample_seed``-seeded,
  shared across backends so they stay bit-identical), mirroring the
  paper's observation that one machine cannot materialize 68B pairs.
- the paper's strictly-upper-triangular pair *bitmap* encoding
  ``b(i,j,n) = i*(n-1) - (i-1)*i/2 + j - i - 1`` for compactly shipping a
  filtered subset of a block's pairs to pairwise matching.

Measured on a CPU backend only (benchmarks/bench_pairs.py, 1M pair
slots): the numpy path is enumeration-bound and the device path
sort-bound; the crossover is around ~10k pair slots — below that, jit
dispatch overhead dominates and ``backend="numpy"`` wins; above it the
JAX path is ~5.6x faster on many-small-block layouts (the shift method's
worst case: one pass per diagonal offset), ~5.2x on medium (16-64) and
~2.4-2.5x on large/zipf layouts where numpy's per-block meshgrid path is
less penalized. That crossover is a CPU number, so ``"auto"`` applies
it on the CPU backend only; on an accelerator every pair set takes the
device path. Pallas interpret-mode timings are parity checks only.

sort_backend (the dedupe-sort knob, threaded through every device
dedupe call site down to ``kernels/sort``): ``"auto"`` keeps the
per-platform winner — the packed-u64 ``np.sort`` host path on the CPU
backend, the radix engine on real accelerators when rids fit the 62-bit
pack; ``"comparator"`` / ``"radix"`` force XLA's ``lax.sort`` vs the
LSB radix kernel. Measured on this CPU (``bench_pairs.py
--sort-backend radix``, ~300k slots): host np.sort ~4-8x the
comparator, and the comparator ~6x the jnp radix mirror — XLA CPU
lowers the per-pass scatter sequentially, so radix only pays off where
the comparator network's O(log^2 n) shuffle rounds dominate (TPU/GPU);
the knob exists so hardware runs can measure exactly that crossover.
All choices are bit-identical on every parity suite.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .hdb import BlockingResult
from .. import obs
from ..kernels import pairs as pairs_kernels
from ..kernels.pairs import ref as pairs_ref

INT32_MAX = np.iinfo(np.int32).max


@dataclasses.dataclass
class Blocks:
    """Accepted blocks in CSR-ish form, sorted by (key, rid)."""

    key_hi: np.ndarray   # (B,) uint32 block key
    key_lo: np.ndarray   # (B,) uint32
    start: np.ndarray    # (B,) int64 offset into members
    size: np.ndarray     # (B,) int64
    members: np.ndarray  # (M,) int64 rids, sorted within each block

    @property
    def num_blocks(self) -> int:
        return len(self.start)

    @property
    def num_pair_slots(self) -> int:
        """Sum over blocks of C(n,2) — pairs BEFORE cross-block dedupe."""
        return int(np.sum(self.size * (self.size - 1) // 2))


def build_blocks(result: BlockingResult, min_size: int = 2) -> Blocks:
    """Group accepted (rid, key) assignments into blocks."""
    key64 = (result.key_hi.astype(np.uint64) << np.uint64(32)) | result.key_lo.astype(np.uint64)
    order = np.lexsort((result.rids, key64))
    key64 = key64[order]
    rids = result.rids[order]
    if len(key64) == 0:
        z64 = np.zeros((0,), np.int64)
        zu = np.zeros((0,), np.uint32)
        return Blocks(zu, zu, z64, z64, z64)
    starts = np.flatnonzero(np.concatenate([[True], key64[1:] != key64[:-1]]))
    sizes = np.diff(np.concatenate([starts, [len(key64)]]))
    keep = sizes >= min_size
    starts, sizes = starts[keep], sizes[keep]
    keys = key64[starts]
    return Blocks(
        key_hi=(keys >> np.uint64(32)).astype(np.uint32),
        key_lo=(keys & np.uint64(0xFFFFFFFF)).astype(np.uint32),
        start=starts.astype(np.int64),
        size=sizes.astype(np.int64),
        members=rids,
    )


def iter_block_pairs(blocks: Blocks, chunk_pairs: int = 2_000_000
                     ) -> Iterator[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """Yield (a, b, block_size) pair chunks across all blocks (HOST path).

    This is the numpy reference enumeration. Small blocks are emitted with
    the vectorized shift method: for offset d, every element pairs with
    the element d positions later iff both are in the same block. Large
    blocks fall back to per-block meshgrid emission. Chunk ORDER differs
    from the canonical slot order of the device engine; only the deduped
    pair *set* is order-canonical.
    """
    small_cut = 64
    small = blocks.size <= small_cut
    # --- small blocks: shift method over one concatenated array ---
    if np.any(small):
        s_start = blocks.start[small]
        s_size = blocks.size[small]
        total = int(s_size.sum())
        # vectorized gather of each kept block's member range
        offs = np.arange(total) - np.repeat(np.cumsum(s_size) - s_size, s_size)
        mem = blocks.members[np.repeat(s_start, s_size) + offs]
        seg = np.repeat(np.arange(len(s_size)), s_size)
        bsz = np.repeat(s_size, s_size)
        max_d = int(s_size.max())
        buf_a, buf_b, buf_s, buffered = [], [], [], 0
        for d in range(1, max_d):
            ok = seg[d:] == seg[:-d]
            if not ok.any():
                continue
            buf_a.append(mem[:-d][ok])
            buf_b.append(mem[d:][ok])
            buf_s.append(bsz[:-d][ok])
            buffered += int(ok.sum())
            if buffered >= chunk_pairs:
                yield np.concatenate(buf_a), np.concatenate(buf_b), np.concatenate(buf_s)
                buf_a, buf_b, buf_s, buffered = [], [], [], 0
        if buffered:
            yield np.concatenate(buf_a), np.concatenate(buf_b), np.concatenate(buf_s)
    # --- large blocks: per-block triangular emission ---
    for bi in np.flatnonzero(~small):
        s, n = int(blocks.start[bi]), int(blocks.size[bi])
        m = blocks.members[s : s + n]
        ii, jj = np.triu_indices(n, 1)
        for off in range(0, len(ii), chunk_pairs):
            sl = slice(off, off + chunk_pairs)
            yield m[ii[sl]], m[jj[sl]], np.full(len(ii[sl]), n, np.int64)


@dataclasses.dataclass
class PairSet:
    """Distinct pairs with largest-source-block provenance."""

    a: np.ndarray          # (P,) int64, a < b, sorted by (a, b)
    b: np.ndarray          # (P,) int64
    src_size: np.ndarray   # (P,) int64 size of largest block producing the pair
    exact: bool            # False => uniform slot sampling (budget exceeded)
    total_slots: int       # sum C(n,2) before dedupe
    # device-resident (a, b) from the device dedupe path, when it ran —
    # lets the matcher consume the pair buffer without a host round trip
    device_a: Optional[jax.Array] = None
    device_b: Optional[jax.Array] = None
    # routed distributed dedupe only: its rounds' exchanges
    # (``core.distributed.Exchange``), and why the single-device engine
    # made this set instead, where it did
    exchanges: list = dataclasses.field(default_factory=list)
    fallback: Optional[str] = None

    def pair_buffers(self):
        """(a, b) as device arrays; zero-copy when the device engine
        produced them, a single upload otherwise."""
        if self.device_a is not None:
            return self.device_a, self.device_b
        # pre-cast host-side: uploading int64 under x64-off would be a
        # dtype-coercing implicit transfer (repro.analysis R001)
        return (jnp.asarray(np.asarray(self.a, np.int32)),
                jnp.asarray(np.asarray(self.b, np.int32)))


# ---------------------------------------------------------------------------
# Backend selection + sampling fallback (shared host plumbing)
# ---------------------------------------------------------------------------

_BACKENDS = ("auto", "numpy", "jax", "pallas", "distributed")
_SORT_BACKENDS = ("auto", "comparator", "radix")
# below this many pair slots, jit dispatch overhead beats the numpy loop
# (measured crossover, see module docstring); "auto" stays host-side there
_AUTO_NUMPY_CROSSOVER = 10_000


def _device_contract_ok(blocks: Blocks, budget: int) -> Optional[str]:
    """None if the int32 device engine applies, else the reason it doesn't."""
    if budget >= INT32_MAX:
        return f"budget {budget} >= int32 max"
    if blocks.num_blocks == 0:
        return None
    max_n = int(blocks.size.max())
    if max_n > pairs_kernels.MAX_BLOCK_N:
        return f"block size {max_n} > MAX_BLOCK_N {pairs_kernels.MAX_BLOCK_N}"
    if len(blocks.members) and int(blocks.members.max()) >= INT32_MAX:
        return "record ids >= int32 max"
    return None


def resolve_backend(backend: str, blocks: Blocks, budget: int) -> str:
    """The single-device pairs backend that ``backend`` runs as here.

    ``"auto"`` is ``"jax"`` unless the layout is below the CPU crossover
    on the CPU backend. Any device backend whose int32 contract fails
    warns and runs ``"numpy"`` — never silently.
    """
    if backend not in _BACKENDS:
        raise ValueError(f"backend must be one of {_BACKENDS}, got {backend!r}")
    assert backend != "distributed"  # dispatched before resolution
    if backend == "numpy":
        return "numpy"
    if (backend == "auto" and blocks.num_pair_slots < _AUTO_NUMPY_CROSSOVER
            and jax.default_backend() == "cpu"):
        return "numpy"
    reason = _device_contract_ok(blocks, budget)
    if reason is None:
        return "jax" if backend == "auto" else backend
    warnings.warn(f"pairs backend {backend!r} unavailable ({reason}); "
                  "falling back to numpy", RuntimeWarning, stacklevel=3)
    return "numpy"


def _sample_slots(total: int, budget: int, seed: int) -> np.ndarray:
    """Deterministic uniform pair-slot sample (shared across backends).

    Returns exactly ``min(budget, total)`` sorted distinct int64 slot
    indices, allocating O(budget) memory regardless of ``total`` (the
    slot space reaches 68B pairs at paper scale — materializing it, as a
    full permutation would, is off the table). Dense draws
    (``2 * budget >= total``) permute the slot range, which is already
    O(budget); sparse draws reject duplicates in geometrically-growing
    with-replacement rounds and then subsample the distinct set
    uniformly — by slot exchangeability that is an exact uniform draw
    without replacement.
    """
    rng = np.random.default_rng(seed)
    budget = max(0, min(budget, total))
    if budget == 0:
        return np.zeros((0,), np.int64)
    if 2 * budget >= total:
        return np.sort(rng.permutation(total)[:budget]).astype(np.int64)
    uniq = np.zeros((0,), np.int64)
    while len(uniq) < budget:
        need = budget - len(uniq)
        draws = rng.integers(0, total, size=int(need * 1.1) + 16,
                             dtype=np.int64)
        uniq = np.unique(np.concatenate([uniq, draws]))
    if len(uniq) > budget:
        # subsample uniformly — truncating the SORTED uniques would
        # systematically exclude the top of the slot space
        uniq = np.sort(uniq[rng.choice(len(uniq), budget, replace=False)])
    return uniq


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _empty_pairset(exact: bool, total: int) -> PairSet:
    z = np.zeros((0,), np.int64)
    return PairSet(z, z, z, exact, total)


# ---------------------------------------------------------------------------
# Backend pair-materialization paths
# ---------------------------------------------------------------------------


def _dedupe_numpy(blocks: Blocks, slots: Optional[np.ndarray]) -> Tuple[np.ndarray, ...]:
    """Host reference: full shift-method enumeration (exact path) or
    canonical slot decode (sampled path), then lexsort dedupe."""
    if slots is None:
        chunks = list(iter_block_pairs(blocks))
        if not chunks:
            z = np.zeros((0,), np.int64)
            return z, z, z
        a = np.concatenate([np.minimum(ca, cb) for ca, cb, _ in chunks])
        b = np.concatenate([np.maximum(ca, cb) for ca, cb, _ in chunks])
        s = np.concatenate([cs for _, _, cs in chunks])
    else:
        a, b, s = pairs_ref.decode_slots_ref(
            blocks.start, blocks.size, blocks.members, slots)
    return pairs_ref.dedupe_ref(a, b, s)


def _packable(blocks: Blocks) -> bool:
    """Do all rids fit the 62-bit sort-word layout?"""
    return (len(blocks.members) == 0
            or int(blocks.members.max()) < (1 << pairs_kernels.PACK_RID_BITS))


def _radix_passes_for_blocks(blocks: Blocks) -> int:
    """Static radix pass count covering this layout's packed sort words
    (single source for every radix call site — an under-covered pass
    count would silently mis-sort high rid bits)."""
    return pairs_kernels.radix_passes_for(
        int(blocks.members.max()) if len(blocks.members) else 0)


def resolve_sort_backend(sort_backend: str, blocks: Blocks) -> str:
    """Map the user knob onto a concrete dedupe-sort strategy.

    Returns one of "host" (packed u64 ``np.sort`` — CPU only, where host
    memory IS device memory), "radix" (``kernels.sort`` LSB radix over
    packed words), or "comparator" (``lax.sort``). ``"auto"`` keeps the
    measured winner per platform: the host sort on CPU, radix on real
    accelerators when the rids fit the 62-bit pack, comparator otherwise.
    Forcing ``"radix"`` beyond the pack bound warns and degrades to the
    comparator (the only order-preserving option there).
    """
    if sort_backend not in _SORT_BACKENDS:
        raise ValueError(f"sort_backend must be one of {_SORT_BACKENDS}, "
                         f"got {sort_backend!r}")
    packable = _packable(blocks)
    on_cpu = jax.default_backend() == "cpu"
    if sort_backend == "auto":
        if on_cpu and packable:
            return "host"
        return "radix" if packable else "comparator"
    if sort_backend == "radix" and not packable:
        warnings.warn(
            "sort_backend='radix' needs rids < "
            f"2**{pairs_kernels.PACK_RID_BITS} to pack the 62-bit sort "
            "word; using the comparator sort", RuntimeWarning, stacklevel=4)
        return "comparator"
    return sort_backend


def _decode_device(blocks: Blocks, slots: Optional[np.ndarray], total: int,
                   chunk_pairs: int, use_kernel: bool) -> Tuple[list, ...]:
    """Device slot decode in fixed-shape chunks: every slot of
    ``[0, total)`` (exact path) or the sampled ``slots``. Returns the
    chunks' ``a``, ``b``, ``size`` and ``valid`` device arrays, as four
    lists."""
    # host-side casts + explicit uploads: dtype-coercing jnp.asarray and
    # jnp.int32(py_scalar) are implicit host->device transfers (rejected
    # under jax.transfer_guard("disallow") — repro.analysis R001)
    start32 = jnp.asarray(blocks.start.astype(np.int32))
    size32 = jnp.asarray(blocks.size.astype(np.int32))
    mem32 = jnp.asarray(blocks.members.astype(np.int32))
    steps = pairs_kernels.search_steps_for(int(blocks.size.max()))
    out_a, out_b, out_s, out_v = [], [], [], []
    if slots is None:
        # exact path: enumerate [0, total) on device
        cum = pairs_ref.cum_pair_counts(blocks.size)
        cum32 = jnp.asarray(cum.astype(np.int32))
        chunk = min(chunk_pairs, _round_up(max(total, 1), 1024))
        total32 = jax.device_put(np.int32(total))
        for base in range(0, total, chunk):
            a, b, s, v = pairs_kernels.decode_chunk(
                cum32, start32, size32, mem32,
                jax.device_put(np.int32(base)), total32,
                chunk=chunk, steps=steps, use_kernel=use_kernel)
            out_a.append(a); out_b.append(b); out_s.append(s); out_v.append(v)
    else:
        # sampled path: slots are int64 host-side; split block/local on
        # host (global indices overflow int32), decode on device
        cum = pairs_ref.cum_pair_counts(blocks.size)
        block = np.searchsorted(cum, slots, side="right") - 1
        local = (slots - cum[block]).astype(np.int32)
        block = block.astype(np.int32)
        chunk = min(chunk_pairs, _round_up(max(len(slots), 1), 1024))
        pad = (-len(slots)) % chunk
        valid = np.ones(len(slots), bool)
        if pad:
            block = np.pad(block, (0, pad))
            local = np.pad(local, (0, pad))
            valid = np.pad(valid, (0, pad))
        for off in range(0, len(block), chunk):
            sl = slice(off, off + chunk)
            a, b, s, v = pairs_kernels.decode_block_local(
                start32, size32, mem32, jnp.asarray(block[sl]),
                jnp.asarray(local[sl]), jnp.asarray(valid[sl]),
                steps=steps, use_kernel=use_kernel)
            out_a.append(a); out_b.append(b); out_s.append(s); out_v.append(v)
    return out_a, out_b, out_s, out_v


def _sort_device(blocks: Blocks, decoded: Tuple[list, ...], use_kernel: bool,
                 sort_backend: str = "auto") -> Tuple[np.ndarray, ...]:
    """One sort-dedupe pass over the chunks ``_decode_device`` returned.

    The dedupe sort strategy comes from ``resolve_sort_backend``:
    ``"auto"`` packs the words on device and sorts with ``np.sort`` on
    the CPU backend (host == device memory there, and numpy's u64 sort
    is ~40x faster than XLA CPU's comparator sort) and radix-sorts on
    device elsewhere; ``"comparator"``/``"radix"`` force the device sort
    flavor (useful to exercise and benchmark either on any platform).
    """
    out_a, out_b, out_s, out_v = decoded
    if not out_a:
        z = np.zeros((0,), np.int64)
        return z, z, z, None
    sort_kind = resolve_sort_backend(sort_backend, blocks)
    if sort_kind == "host":
        his, los = [], []
        for a, b, s, v in zip(out_a, out_b, out_s, out_v):
            hi, lo = pairs_kernels.pack_sort_words(a, b, s, v)
            his.append(np.asarray(hi)); los.append(np.asarray(lo))
        return pairs_kernels.dedupe_packed_host(
            np.concatenate(his), np.concatenate(los)) + (None,)
    # n_passes is a static jit arg: derive it from the data only when the
    # radix sort actually consumes it, so comparator graphs don't retrace
    # as the rid span crosses digit boundaries
    kw = {}
    if sort_kind == "radix":
        kw["n_passes"] = _radix_passes_for_blocks(blocks)
    sa, sb, ss, winner = pairs_kernels.dedupe_device(
        jnp.concatenate(out_a), jnp.concatenate(out_b),
        jnp.concatenate(out_s), jnp.concatenate(out_v),
        sort_backend=sort_kind, use_kernel=use_kernel, **kw)
    # compact host-side (the winner count is data-dependent, so the mask
    # gather can't stay on device without a dynamic shape; indexing the
    # device array with a host mask would be an implicit transfer) and
    # re-upload the compacted buffers explicitly for device consumers
    w = np.asarray(winner)
    a_host = np.asarray(sa)[w]
    b_host = np.asarray(sb)[w]
    dev = (jnp.asarray(a_host), jnp.asarray(b_host))
    return (a_host.astype(np.int64), b_host.astype(np.int64),
            np.asarray(ss)[w].astype(np.int64), dev)


def dedupe_pairs(blocks: Blocks, budget: int = 50_000_000,
                 backend: str = "auto", chunk_pairs: int = 1 << 20,
                 sample_seed: int = 0,
                 mesh=None, axis_names: Tuple[str, ...] = ("data",),
                 route_slack: float = 2.0,
                 sort_backend: str = "auto") -> PairSet:
    """RemoveDupePairs: distinct (a, b), keeping the largest source block.

    Within ``budget`` total pair slots the result is exact; beyond it the
    engine decodes a deterministic uniform sample of ``budget`` slots
    (``exact=False``) — counting stays exact via ``total_slots``. All
    backends produce bit-identical PairSets for the same arguments; see
    the module docstring for the backend/chunking contract.

    ``sort_backend`` selects the dedupe-sort engine of the device
    backends (``"comparator"`` = ``lax.sort``, ``"radix"`` = the
    ``kernels/sort`` LSB radix kernel over packed words, ``"auto"`` =
    the measured per-platform winner — see ``resolve_sort_backend``);
    every choice is bit-identical, only speed differs (measured
    crossover in the module docstring). The numpy backend ignores it.

    ``backend="distributed"`` routes through the fingerprint-routed
    shard-local dedupe over ``mesh`` (all local devices on one "data"
    axis when ``mesh`` is None) — see
    ``core.distributed.dedupe_pairs_distributed`` for the contract;
    ``chunk_pairs`` becomes the per-shard chunk and the budget sample
    stays the seeded global one, so results remain bit-identical to
    every single-device backend.
    """
    if sort_backend not in _SORT_BACKENDS:
        # validate eagerly: the numpy shortcut below never consults the
        # knob, and a typo must not pass on small workloads only
        raise ValueError(f"sort_backend must be one of {_SORT_BACKENDS}, "
                         f"got {sort_backend!r}")
    total = blocks.num_pair_slots
    if total == 0:
        return _empty_pairset(True, total)
    if backend == "distributed":
        from . import distributed as dist_lib
        if mesh is None:
            mesh = jax.make_mesh((len(jax.devices()),), ("data",))
            axis_names = ("data",)
        return dist_lib.dedupe_pairs_distributed(
            blocks, mesh, axis_names, budget=budget,
            chunk_per_shard=chunk_pairs, route_slack=route_slack,
            sample_seed=sample_seed, sort_backend=sort_backend)
    exact = total <= budget
    backend = resolve_backend(backend, blocks, budget)
    if backend == "numpy":
        slots = None if exact else _sample_slots(total, budget, sample_seed)
        a, b, s = _dedupe_numpy(blocks, slots)
        return PairSet(a, b, s, exact, total)
    use_kernel = backend == "pallas"
    with obs.span("repro.pairs.decode"):
        slots = None if exact else _sample_slots(total, budget, sample_seed)
        decoded = _decode_device(blocks, slots, total, chunk_pairs,
                                 use_kernel)
    with obs.span("repro.pairs.sort", slots=min(total, budget)):
        a, b, s, dev = _sort_device(blocks, decoded, use_kernel, sort_backend)
    obs.mark("repro.pairs.sort.counts", pairs=len(a))
    return PairSet(a, b, s, exact, total,
                   device_a=None if dev is None else dev[0],
                   device_b=None if dev is None else dev[1])


def enumerate_pairs(blocks: Blocks, backend: str = "auto",
                    chunk_pairs: int = 1 << 20
                    ) -> Iterator[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """Stream raw (a, b, block_size) numpy chunks WITHOUT dedupe.

    Device backends decode the canonical slot order in fixed-shape
    chunks; the numpy backend streams the legacy shift-method order.
    Used by consumers that need multiplicities (e.g. meta-blocking's CBS
    edge weighting) rather than the deduped pair set.
    """
    if backend == "distributed":
        raise ValueError(
            "enumerate_pairs streams raw pre-dedupe chunks and has no "
            "distributed backend; use dedupe_pairs(backend='distributed') "
            "or a single-device backend here")
    # enumeration is always exact, so the WHOLE slot space must fit the
    # device's int32 slot indices (dedupe_pairs only needs budget to fit —
    # its sampled path never materializes global slot indices on device);
    # min() maps an overflowing total onto the budget >= INT32_MAX check.
    backend = resolve_backend(backend, blocks,
                               budget=min(blocks.num_pair_slots, INT32_MAX))
    if backend == "numpy":
        yield from iter_block_pairs(blocks, chunk_pairs)
        return
    total = blocks.num_pair_slots
    if total == 0:
        return
    cum32 = jnp.asarray(pairs_ref.cum_pair_counts(blocks.size).astype(np.int32))
    start32 = jnp.asarray(blocks.start.astype(np.int32))
    size32 = jnp.asarray(blocks.size.astype(np.int32))
    mem32 = jnp.asarray(blocks.members.astype(np.int32))
    steps = pairs_kernels.search_steps_for(int(blocks.size.max()))
    chunk = min(chunk_pairs, _round_up(max(total, 1), 1024))
    total32 = jax.device_put(np.int32(total))
    for base in range(0, total, chunk):
        a, b, s, v = pairs_kernels.decode_chunk(
            cum32, start32, size32, mem32,
            jax.device_put(np.int32(base)), total32,
            chunk=chunk, steps=steps, use_kernel=(backend == "pallas"))
        vm = np.asarray(v)
        yield (np.asarray(a)[vm].astype(np.int64),
               np.asarray(b)[vm].astype(np.int64),
               np.asarray(s)[vm].astype(np.int64))


# ---------------------------------------------------------------------------
# Triangular pair bitmap (paper §3.1 equation for b_{i,j})
# ---------------------------------------------------------------------------


def pair_bit_index(i: np.ndarray, j: np.ndarray, n: int) -> np.ndarray:
    """Bit index of pair (i, j), i < j, in the C(n,2) upper-triangular map."""
    i = np.asarray(i, np.int64)
    j = np.asarray(j, np.int64)
    return i * (n - 1) - (i - 1) * i // 2 + j - i - 1


def pair_from_bit_index(bit: np.ndarray, n: int) -> Tuple[np.ndarray, np.ndarray]:
    """Inverse of pair_bit_index (vectorized)."""
    bit = np.asarray(bit, np.int64)
    # row i satisfies cum(i) <= bit < cum(i+1), cum(i) = i*(n-1) - (i-1)i/2
    i_all = np.arange(n, dtype=np.int64)
    cum = i_all * (n - 1) - (i_all - 1) * i_all // 2
    i = np.searchsorted(cum, bit, side="right") - 1
    j = bit - cum[i] + i + 1
    return i, j


def build_pair_bitmap(n: int, kept_i: np.ndarray, kept_j: np.ndarray) -> np.ndarray:
    """Packed uint8 bitmap of C(n,2) bits with the kept pairs set."""
    nbits = n * (n - 1) // 2
    bits = np.zeros(nbits, np.uint8)
    bits[pair_bit_index(kept_i, kept_j, n)] = 1
    return np.packbits(bits)


def read_pair_bitmap(n: int, bitmap: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    nbits = n * (n - 1) // 2
    bits = np.unpackbits(bitmap, count=nbits)
    return pair_from_bit_index(np.flatnonzero(bits), n)


# ---------------------------------------------------------------------------
# Membership utilities for recall (PC) evaluation without full materialization
# ---------------------------------------------------------------------------


def pair_covered(result: BlockingResult, pairs_a: np.ndarray, pairs_b: np.ndarray
                 ) -> np.ndarray:
    """For labeled pairs (a, b): does any accepted block contain both?

    Evaluated via a hash set of (key, rid) assignments — no pair
    materialization, so it works at any scale (used for PC on datasets
    whose full pair set exceeds the budget).
    """
    key64 = (result.key_hi.astype(np.uint64) << np.uint64(32)) | result.key_lo.astype(np.uint64)
    assign = np.stack([key64, result.rids.astype(np.uint64)], axis=1)
    # dictionary of key -> sorted rid ranges via lexsort
    order = np.lexsort((assign[:, 1], assign[:, 0]))
    k_sorted = assign[order, 0]
    r_sorted = assign[order, 1]
    covered = np.zeros(len(pairs_a), bool)
    # group keys of record a: need per-record key lists -> sort by rid
    order_r = np.lexsort((key64, result.rids))
    rid_sorted = result.rids[order_r]
    key_by_rid = key64[order_r]
    for idx, (a, b) in enumerate(zip(pairs_a, pairs_b)):
        lo = np.searchsorted(rid_sorted, a, "left")
        hi = np.searchsorted(rid_sorted, a, "right")
        for key in key_by_rid[lo:hi]:
            klo = np.searchsorted(k_sorted, key, "left")
            khi = np.searchsorted(k_sorted, key, "right")
            pos = np.searchsorted(r_sorted[klo:khi], np.uint64(b))
            if pos < khi - klo and r_sorted[klo + pos] == np.uint64(b):
                covered[idx] = True
                break
    return covered
