"""Tile autotuner — per-permutation, per-shape (bm, bn, bk) block-shape
search with a persistent JSON cache.

The paper hand-schedules each of its 27 kernels for GAP-8's TCDM; the TPU
analogue of that scheduling freedom is the VMEM block shape. The right
(bm, bn, bk) depends on the permutation (pack ratios change the packed block
footprint and the unpack work per MXU call) and on the problem shape (decode
GEMV wants tiny bm; prefill wants large square tiles), so winners are cached
per ``(op, permutation, shape)``.

Cache discipline:
  * winners persist to ``benchmarks/tuned/tiles_<op>.json`` (checked into the
    repo — the cache IS the tuned library, and CI diffs benchmark output
    against it),
  * :func:`resolve_tiles` is the single read path ops.py uses on every call:
    explicit caller overrides > cached winner > static defaults,
  * the static default is always part of the candidate set, so an autotuned
    winner can only match or beat it — untuned and tuned runs are both safe.

Off-repo installs (no writable ``benchmarks/tuned/``) degrade to the static
defaults; set ``REPRO_TUNED_DIR`` to relocate the cache.
"""

from __future__ import annotations

import json
import os
import pathlib
import time
from typing import Callable, Optional, Sequence

CACHE_FORMAT = "repro-tile-cache-v1"


class IllegalTile(ValueError):
    """A kernel was handed blocks that cannot tile its operands. The one
    error :func:`autotune` treats as "skip this candidate"; a compiler error
    is a fault and propagates."""

#: The pre-registry hand-picked blocks (mpmm.py's VMEM working-set math).
STATIC_DEFAULTS: dict[str, dict[str, int]] = {
    "mpmm": {"bm": 256, "bn": 256, "bk": 512},
    "wdqmm": {"bm": 256, "bn": 256, "bk": 512},
    "qntpack": {"bm": 256},
    # conv2d's tunable axis is the output-row block per grid step (the
    # im2col+MatMul call gets bh*W rows tall); bh=1 is the pre-registry
    # one-row-per-step schedule.
    "conv2d": {"bh": 1},
    # the paged KV cache's page size (tokens per page). Small pages waste
    # less tail capacity per request (internal fragmentation ~ ps/2 tokens);
    # large pages amortize gather/scatter grid steps — a tile trade-off, so
    # it resolves through the same cache as the matmul blocks
    # (serve.cache.PagedKVCache consults resolve_tiles("kvpage", ...)).
    "kvpage": {"ps": 16},
    # fused decode attention's dense-view block size: the slot backend
    # reshapes its (B, S_max, ...) stripes into a (B*S_max/bs, bs, ...)
    # page-pool view, so bs plays exactly the page-size role — and the
    # default matches kvpage's ps so slot/paged outputs stay bit-identical.
    "paged_attn": {"bs": 16},
}

#: Candidate menus per tunable axis. ops.py clamps to the (padded) problem
#: shape, so oversized candidates just collapse onto the whole-problem tile;
#: duplicates after clamping are pruned by the tuner.
_BM_MENU = (8, 16, 32, 64, 128, 256)
_BN_MENU = (32, 64, 128, 256)
_BK_MENU = (64, 128, 256, 512)
_BH_MENU = (1, 2, 4, 8)
_PS_MENU = (4, 8, 16, 32, 64)


def default_cache_dir() -> pathlib.Path:
    env = os.environ.get("REPRO_TUNED_DIR")
    if env:
        return pathlib.Path(env)
    return pathlib.Path(__file__).resolve().parents[3] / "benchmarks" / "tuned"


def backend() -> str:
    """Cache namespace for tuned winners. Tiles tuned under CPU interpret
    mode measure interpreter overhead, not MXU schedules — a TPU must never
    inherit them (it falls back to static defaults until tuned natively)."""
    import jax

    return jax.default_backend()


def perm_key(x_bits: Optional[int] = None, w_bits: Optional[int] = None,
             y_bits: Optional[int] = None) -> str:
    """Cache key segment for a precision cell, e.g. ``u8_i4_u2`` / ``i4``."""
    parts = []
    if x_bits is not None:
        parts.append(f"u{x_bits}")
    if w_bits is not None:
        parts.append(f"i{w_bits}")
    if y_bits is not None:
        parts.append(f"u{y_bits}")
    return "_".join(parts) or "any"


def shape_key(M: int, N: Optional[int] = None, K: Optional[int] = None) -> str:
    s = f"M{M}"
    if N is not None:
        s += f"_N{N}"
    if K is not None:
        s += f"_K{K}"
    return s


class TileCache:
    """One op's tuned-tile store, mirrored to a JSON file."""

    def __init__(self, op: str, path: Optional[pathlib.Path] = None):
        self.op = op
        self.path = path or (default_cache_dir() / f"tiles_{op}.json")
        self.entries: dict[str, dict] = {}
        self._loaded = False

    def _load(self) -> None:
        if self._loaded:
            return
        self._loaded = True
        try:
            doc = json.loads(self.path.read_text())
        except (OSError, ValueError):
            return
        if doc.get("format") == CACHE_FORMAT and doc.get("op") == self.op:
            self.entries = dict(doc.get("entries", {}))

    def get(self, perm: str, shape: str) -> Optional[dict]:
        self._load()
        hit = self.entries.get(f"{backend()}/{perm}/{shape}")
        return dict(hit) if hit else None

    def put(self, perm: str, shape: str, tiles: dict, us: float,
            source: str = "autotune", persist: bool = True) -> None:
        self._load()
        self.entries[f"{backend()}/{perm}/{shape}"] = {
            **tiles, "us": round(us, 3), "source": source,
        }
        if persist:
            self.save()

    def save(self) -> None:
        # persist only into an explicit REPRO_TUNED_DIR or an existing
        # benchmarks/tuned/ (a repo checkout) — a pip-installed package must
        # not scribble a benchmarks/ tree next to site-packages
        if "REPRO_TUNED_DIR" in os.environ:
            try:
                self.path.parent.mkdir(parents=True, exist_ok=True)
            except OSError:
                return
        elif not self.path.parent.is_dir():
            return
        doc = {
            "format": CACHE_FORMAT,
            "op": self.op,
            "entries": {k: self.entries[k] for k in sorted(self.entries)},
        }
        try:
            tmp = self.path.with_suffix(".json.tmp")
            tmp.write_text(json.dumps(doc, indent=1, sort_keys=True) + "\n")
            tmp.replace(self.path)
        except OSError:
            pass  # read-only location: stay in-memory


_CACHES: dict[str, TileCache] = {}


def get_cache(op: str) -> TileCache:
    if op not in _CACHES:
        _CACHES[op] = TileCache(op)
    return _CACHES[op]


def reset_caches() -> None:
    """Drop memoized caches (tests / REPRO_TUNED_DIR changes)."""
    _CACHES.clear()


def resolve_tiles(
    op: str,
    *,
    perm: str,
    shape: str,
    overrides: Optional[dict] = None,
) -> dict[str, int]:
    """The per-call tile decision: overrides > tuned cache > static default."""
    tiles = dict(STATIC_DEFAULTS[op])
    hit = get_cache(op).get(perm, shape)
    if hit:
        tiles.update({k: int(hit[k]) for k in tiles if k in hit})
    if overrides:
        tiles.update({k: int(v) for k, v in overrides.items() if v is not None})
    return tiles


def candidates(op: str, *, M: int, N: Optional[int] = None,
               K: Optional[int] = None) -> list[dict[str, int]]:
    """Candidate tile set for a problem shape; static default always first."""
    static = STATIC_DEFAULTS[op]
    out, seen = [], set()

    def clamp(menu: Sequence[int], size: Optional[int], align: int) -> list[int]:
        if size is None:
            return list(menu)
        cap = -(-size // align) * align  # the op pads up to this
        vals = sorted({min(v, cap) for v in menu})
        return vals

    if op == "qntpack":
        grid = [{"bm": bm} for bm in clamp(_BM_MENU, M, 8)]
    elif op == "kvpage":
        # M is the cache's s_max: pages larger than the whole sequence
        # budget only add dead tail capacity
        grid = [{"ps": ps} for ps in _PS_MENU if ps <= M]
    elif op == "paged_attn":
        # M is the dense cache's S_max; ops.paged_attn snaps bs to a divisor
        # of S_max (the reshape to a page-pool view must tile exactly)
        grid = [{"bs": bs} for bs in _PS_MENU + (128,)
                if bs <= M and M % bs == 0]
    elif op == "conv2d":
        # M is the ofmap height here; ops.conv2d snaps bh to a divisor of H,
        # so non-dividing candidates would silently duplicate smaller ones.
        grid = [{"bh": bh} for bh in _BH_MENU if bh <= M and M % bh == 0]
    else:
        bms = clamp(_BM_MENU, M, 8)
        bns = clamp(_BN_MENU, N, 128)
        bks = clamp(_BK_MENU, K, 128)
        # cross product pruned to a budgeted sweep: full bk sweep at the
        # default bm/bn, full bm/bn grid at the default bk
        grid = [{"bm": min(static["bm"], bms[-1]), "bn": min(static["bn"], bns[-1]), "bk": bk}
                for bk in bks]
        grid += [{"bm": bm, "bn": bn, "bk": min(static["bk"], bks[-1])}
                 for bm in bms for bn in bns]
    ordered = [dict(static)] + grid
    for t in ordered:
        key = tuple(sorted(t.items()))
        if key not in seen:
            seen.add(key)
            out.append(t)
    return out


def time_call(fn: Callable[[], object], *, iters: int = 5, warmup: int = 2) -> float:
    """Median wall-time (us) of a zero-arg jax call (blocks until ready)."""
    import jax
    import numpy as np

    for _ in range(warmup):
        jax.block_until_ready(fn())
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(ts))


def time_pair(fn_a: Callable[[], object], fn_b: Callable[[], object], *,
              iters: int = 5, warmup: int = 1) -> tuple[float, float]:
    """Median wall-times (us) of two calls, sampled interleaved — robust to
    machine-load drift, which back-to-back timing is not. This is how the
    benchmark gate compares static vs tuned tiles fairly."""
    import jax
    import numpy as np

    for _ in range(warmup):
        jax.block_until_ready(fn_a())
        jax.block_until_ready(fn_b())
    ta, tb = [], []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn_a())
        ta.append((time.perf_counter() - t0) * 1e6)
        t0 = time.perf_counter()
        jax.block_until_ready(fn_b())
        tb.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(ta)), float(np.median(tb))


def frozen() -> bool:
    """True when the tuned cache is read-only (``REPRO_TUNE_FROZEN=1``).

    CI's bench-smoke job sets this so its baseline diff is meaningful: the
    run must consume the checked-in winners verbatim, never search or
    rewrite them (a gate that regenerates its own baseline cannot fail)."""
    return os.environ.get("REPRO_TUNE_FROZEN", "") not in ("", "0")


def autotune(
    op: str,
    *,
    perm: str,
    shape: str,
    make_call: Callable[[dict], Callable[[], object]],
    cand: Optional[list[dict]] = None,
    iters: int = 5,
    warmup: int = 2,
    persist: bool = True,
    force: bool = False,
) -> dict:
    """Search the candidate tiles for one (op, permutation, shape) cell.

    ``make_call(tiles)`` must return a zero-arg callable running the kernel
    with those tiles. Returns the winning cache entry (tiles + ``us``); reuses
    an existing cached winner unless ``force``. Under :func:`frozen` no search
    or persistence happens: the cached winner (or the static default) is
    returned as-is.
    """
    cache = get_cache(op)
    if frozen():
        return cache.get(perm, shape) or {**STATIC_DEFAULTS[op], "source": "static"}
    if not force:
        hit = cache.get(perm, shape)
        if hit:
            return hit
    if cand is None:
        raise ValueError("autotune needs an explicit candidate list (candidates(op, ...))")
    best_tiles, best_us, last_exc = None, float("inf"), None
    for tiles in cand:
        try:
            us = time_call(make_call(tiles), iters=iters, warmup=warmup)
        except IllegalTile as e:
            last_exc = e
            continue
        if us < best_us:
            best_tiles, best_us = tiles, us
    if best_tiles is None:
        raise RuntimeError(
            f"autotune({op}, {perm}, {shape}): every candidate failed"
        ) from last_exc
    cache.put(perm, shape, best_tiles, best_us, persist=persist)
    return cache.get(perm, shape)


def tune_and_compare(
    op: str,
    *,
    perm: str,
    shape: str,
    make_call: Callable[[dict], Callable[[], object]],
    cand: list[dict],
    iters: int = 3,
    warmup: int = 1,
) -> tuple[dict, float, float]:
    """The benchmark-gate protocol: tune (or reuse the cached winner), then
    compare winner vs static defaults with interleaved sampling. A cached
    winner that loses to machine drift is retuned once (static is always a
    candidate, so the fresh winner matches or beats it); under :func:`frozen`
    the retune is skipped and the comparison is purely observational.

    Returns ``(tiles, us_static, us_tuned)``.
    """
    static = dict(STATIC_DEFAULTS[op])
    keys = tuple(static)

    def measure(entry):
        tiles = {k: entry[k] for k in keys}
        us_s, us_t = time_pair(make_call(static), make_call(tiles),
                               iters=max(iters, 5), warmup=warmup)
        return tiles, us_s, us_t

    entry = autotune(op, perm=perm, shape=shape, make_call=make_call,
                     cand=cand, iters=iters, warmup=warmup)
    tiles, us_static, us_tuned = measure(entry)
    if us_tuned > us_static and not frozen():
        entry = autotune(op, perm=perm, shape=shape, make_call=make_call,
                         cand=cand, iters=iters, warmup=warmup, force=True)
        tiles, us_static, us_tuned = measure(entry)
    return tiles, us_static, us_tuned
