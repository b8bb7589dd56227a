"""Dense texture maps as Orfeo ToolBox's HaralickTextureExtraction makes
them: one answer per request of (gh, gw, n_offsets, 7) float32 features,
the seven distinct bands of its "simple" set (``FEATURES``, in OTB's
order) for every stride-1 window of the configuration's ``region_shape``,
binned over the fixed range ``vrange``.

The reference is written from the formulas alone: exact int64 counts of
every window's pairs by ``np.bincount``, symmetrized and normalized in
float64, and the seven features with exact 0 · log 0. The correlation of
a window whose marginal has no spread (σ = 0) is 0, as the program
defines it.

An answer is 28 times its request (469 MB for a 4096² band piece), and
the closed loop keeps every answer of the window for the comparison after
it, more than a host holds. So the engine the cell is served by
(``HeldOnce``) holds each answer once: the client gets every answer as it
was read back, and off the client's path each is compared bit for bit
with the first answer the engine gave for the same request; only one
found equal in every bit is let go, and holds that first answer in its
place. Every answer of the window is still compared with the reference:
one equal in every bit to another has that one's error.
"""

from __future__ import annotations

import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from chipbench import data, kinds
from chipbench import reference as ref

FEATURES = ("asm_energy", "entropy", "correlation", "inverse_difference_moment",
            "contrast", "cluster_shade", "cluster_prominence")
FLOOR = 1e-3
# Window rows the reference counts at a time: every temporary stays under
# ~17 MB, so that it is reused from the heap. Larger ones are mapped and
# unmapped each time, and the chip's host takes unmapped memory back too
# slowly for a reference that churns gigabytes a second (40 GiB were full
# ~25 s into the reference with 134 MB a block).
BLOCK_ROWS = 8
SIGMA2_ZERO = 1e-9
SETTLERS = 4               # helper threads comparing answers
PENDING = 8                # answers awaiting their compare before result() waits
CPU_SHAPE = [40, 150]      # a CPU test's size: 146 window columns, not a multiple of 128


def _workers() -> int:
    return max(1, min(12, os.cpu_count() or 1))


def make_pool(pool, shape, seed):
    return data.make_pool(pool, shape, seed)


def build_engine(cell, config):
    """The engine serving the configuration's spec on its expected backend
    (which a CPU run's ``cpu_cell`` swaps for the CPU's), holding each
    answer once."""
    spec = dict(config["spec"], scheme=config["expect_backend"])
    return HeldOnce(kinds.serve_engine(cell, dict(config, spec=spec), features=FEATURES))


def _grid(shape, spec):
    return [(n - r) // s + 1
            for n, r, s in zip(shape, spec["region_shape"], spec["region_stride"])]


def work(cell, config, pool):
    """ops = 2 L^2 V over every window's in-bounds pairs; bytes = the raw
    request in and the float32 answer out: the work whatever implements
    it."""
    spec = config["spec"]
    offs = ref.offsets(spec["pairs"], spec["ndim"])
    windows = int(np.prod(_grid(cell["shape"], spec)))
    pairs = sum(int(np.prod([r - abs(d) for r, d in zip(spec["region_shape"], off)]))
                for off in offs)
    levels = spec["levels"]
    ops = 2 * levels * levels * windows * pairs
    nbytes = (int(np.prod(cell["shape"])) * pool[0].dtype.itemsize
              + windows * len(offs) * len(FEATURES) * 4)
    return ops, nbytes


# -- the reference ----------------------------------------------------------


def binned(raw, spec) -> np.ndarray:
    """floor((x - lo) / (hi - lo) * L) in float32, clipped to [0, L)."""
    levels = spec["levels"]
    lo, hi = (np.float32(v) for v in spec["vrange"])
    q = np.floor((raw.astype(np.float32) - lo) / (hi - lo) * np.float32(levels))
    return np.clip(q, 0, levels - 1).astype(np.int64)


def pair_codes(q, levels, offset) -> np.ndarray:
    """a · L + b of the pair a at (y, x), b at (y + dy, x + dx), for every
    pair inside the image, anchored at (y, x + max(0, -dx))."""
    assoc, other = ref.pair_slices(q.shape, offset)
    return q[assoc] * levels + q[other]


def window_counts(code, levels, offset, window, gw, rows, drop_pair=False) -> np.ndarray:
    """(L, L, rows, gw) int64 counts P[a, b] of the pairs inside each window
    whose top row is in ``rows``, from the pair codes, by one
    ``np.bincount`` over (pair code · windows + window index): cell first,
    so that every later sum runs along the windows. ``drop_pair`` leaves
    each window's last pair uncounted (a control)."""
    (rh, rw), (dy, dx) = window, offset
    nh, nw = rh - abs(dy), rw - abs(dx)
    i0, i1 = rows
    n = (i1 - i0) * gw
    win = np.arange(n, dtype=np.int64).reshape(i1 - i0, gw)
    taken = [(a, b) for a in range(nh) for b in range(nw)]
    if drop_pair:
        taken = taken[:-1]
    idx = np.stack([code[i0 + a:i1 + a, b:b + gw] * n + win for a, b in taken])
    return np.bincount(idx.ravel(), minlength=levels * levels * n).reshape(
        levels, levels, i1 - i0, gw)


def features(counts, symmetric=True, normalize=True) -> np.ndarray:
    """(L, L, ...) counts → (..., 7) ``FEATURES`` in float64.

    Every sum linear in p is a moment, taken for all windows by one matrix
    product; the centered sums follow from the moments (exact identities,
    in float64 far inside the comparison's limit): with s = i + j and
    μ = μx + μy, Σ (i - μx)(j - μy) p = E[ij] - μx μy, σx² = E[i²] - μx²,
    Σ (s - μ)³ p = E[s³] - 3μ E[s²] + 2μ³ and Σ (s - μ)⁴ p = E[s⁴] -
    4μ E[s³] + 6μ² E[s²] - 3μ⁴. A σ² below ``SIGMA2_ZERO`` is 0: the
    least spread of a marginal that is not one level is over 0.02."""
    levels = counts.shape[0]
    batch = counts.shape[2:]
    p = counts.reshape(levels * levels, -1).astype(np.float64)
    if symmetric:
        p += counts.swapaxes(0, 1).reshape(levels * levels, -1)
    if normalize:
        p /= np.maximum(p.sum(axis=0), 1.0)
    i, j = (g.ravel().astype(np.float64) for g in np.indices((levels, levels)))
    s = i + j
    moments = np.stack([i, j, i * i, j * j, i * j, (i - j) ** 2, 1.0 / (1.0 + (i - j) ** 2),
                        s * s, s ** 3, s ** 4])
    moments = np.einsum("mk,kn->mn", moments, p)   # no BLAS threads under the pool
    mu_x, mu_y, e_ii, e_jj, e_ij, contrast, idm, e_s2, e_s3, e_s4 = moments
    var_x = np.where(e_ii - mu_x ** 2 > SIGMA2_ZERO, e_ii - mu_x ** 2, 0.0)
    var_y = np.where(e_jj - mu_y ** 2 > SIGMA2_ZERO, e_jj - mu_y ** 2, 0.0)
    sd = np.sqrt(var_x * var_y)
    cov = e_ij - mu_x * mu_y
    mu = mu_x + mu_y
    out = {
        "asm_energy": np.einsum("kn,kn->n", p, p),
        "entropy": -np.einsum("kn,kn->n", p, np.log(np.where(p > 0, p, 1.0))),  # 0 log 0 = 0
        "correlation": np.divide(cov, sd, out=np.zeros_like(cov), where=sd > 0),
        "inverse_difference_moment": idm,
        "contrast": contrast,
        "cluster_shade": e_s3 - 3 * mu * e_s2 + 2 * mu ** 3,
        "cluster_prominence": e_s4 - 4 * mu * e_s3 + 6 * mu ** 2 * e_s2 - 3 * mu ** 4,
    }
    return np.stack([out[f] for f in FEATURES], axis=-1).reshape(batch + (len(FEATURES),))


def reference(raw, config, drop_pair=False):
    """The (gh, gw, n_offsets, 7) float64 answer of one raw request, in
    blocks of ``BLOCK_ROWS`` window rows on a pool of threads."""
    spec = config["spec"]
    if tuple(spec["region_stride"]) != (1, 1):
        raise ValueError("the reference serves stride-1 windows")
    levels = spec["levels"]
    window = tuple(spec["region_shape"])
    offs = ref.offsets(spec["pairs"], spec["ndim"])
    q = binned(raw, spec)
    codes = [pair_codes(q, levels, off) for off in offs]
    gh, gw = _grid(raw.shape, spec)
    out = np.empty((gh, gw, len(offs), len(FEATURES)))

    def block(task):
        k, i0 = task
        i1 = min(i0 + BLOCK_ROWS, gh)
        counts = window_counts(codes[k], levels, offs[k], window, gw, (i0, i1), drop_pair)
        out[i0:i1, :, k] = features(counts, spec["symmetric"], spec["normalize"])

    tasks = [(k, i0) for k in range(len(offs)) for i0 in range(0, gh, BLOCK_ROWS)]
    with ThreadPoolExecutor(_workers()) as ex:
        list(ex.map(block, tasks))
    return out


# -- the comparison ---------------------------------------------------------


class Held:
    """An answer's array, and its error against each reference answer it
    has been compared with."""

    __slots__ = ("array", "errors")

    def __init__(self, array):
        self.array = array
        self.errors = {}


class Answer:
    """One answer as the client holds it: ``held`` is its own array, or
    the first answer to the same request once found equal in every bit."""

    __slots__ = ("held",)

    def __init__(self, held: Held):
        self.held = held

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.held.array, dtype)


def _array(got):
    return got.held.array if isinstance(got, Answer) else got


def memory_order(a: np.ndarray):
    """``a`` as a flat view in the order its elements lie in memory, or
    None where they do not lie densely."""
    flat = a.transpose(sorted(range(a.ndim), key=lambda k: -a.strides[k]))
    return flat.reshape(-1) if flat.flags.c_contiguous else None


def same_bits(a, b, chunk: int = 1 << 22) -> bool:
    """Whether two arrays are equal in shape, type and every bit. Two
    arrays laid out alike are compared in the order they lie in memory, a
    chunk at a time into one buffer: an answer read back as a strided view
    of the device's layout is neither copied nor gathered."""
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape or a.dtype != b.dtype:
        return False
    bits = np.dtype(f"u{a.dtype.itemsize}")
    a, b = a.view(bits), b.view(bits)
    x = memory_order(a) if a.strides == b.strides else None
    if x is None:
        return bool(np.array_equal(a, b))
    y = memory_order(b)
    same = np.empty(min(chunk, x.size), bool)
    for s in range(0, x.size, chunk):
        n = min(chunk, x.size - s)
        if not np.equal(x[s:s + n], y[s:s + n], out=same[:n]).all():
            return False
    return True


class HeldOnce:
    """A ``GLCMEngine`` whose ``result`` hands back an ``Answer``: the
    array the engine returned, and off the client's path, on ``SETTLERS``
    helper threads, the first answer to the same request in its place once
    the two are found equal in every bit. ``result`` waits while
    ``PENDING`` answers await their compare, so that the host's memory
    holds whatever the helpers' pace; ``stats()`` adds the answers let go
    (``held_once_let_go``) and that wait (``held_once_wait_us``).
    Everything else is the engine's."""

    def __init__(self, engine, workers: int = SETTLERS, pending: int = PENDING):
        self._engine = engine
        self._sent = {}       # ticket → the request submitted
        self._first = {}      # id(request) → (request, Held of its first answer)
        self._settle = ThreadPoolExecutor(workers, thread_name_prefix="held-once")
        self._room = threading.BoundedSemaphore(pending)
        self._lock = threading.Lock()
        self.let_go = 0
        self.wait_s = 0.0

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def submit(self, image, **kw):
        ticket = self._engine.submit(image, **kw)
        self._sent[ticket] = image
        return ticket

    def result(self, ticket):
        answer = Answer(Held(self._engine.result(ticket)))
        image = self._sent.pop(ticket, None)
        first = self._first.get(id(image))
        if first is None or first[0] is not image:
            self._first[id(image)] = (image, answer.held)
        else:
            t0 = time.monotonic()
            self._room.acquire()
            self.wait_s += time.monotonic() - t0
            self._settle.submit(self._compare, answer, first[1])
        return answer

    def _compare(self, answer: Answer, first: Held) -> None:
        try:
            if same_bits(answer.held.array, first.array):
                answer.held = first
                with self._lock:
                    self.let_go += 1
        finally:
            self._room.release()

    def stats(self):
        out = self._engine.stats()
        for w in out["workloads"].values():
            w.update(held_once_let_go=self.let_go,
                     held_once_wait_us=int(self.wait_s * 1e6))
        return out


def _max_terms(got, want):
    """(largest term, its index) of |got - want| / (|want| + FLOOR) over
    every entry, in blocks of window rows on a pool of threads; (inf,
    None) for a misshapen or non-finite answer."""
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        return float("inf"), None
    step = max(1, (1 << 20) // max(1, int(np.prod(got.shape[1:]))))

    def block(r0):
        g = got[r0:r0 + step].astype(np.float64)
        if not np.all(np.isfinite(g)):
            return float("inf"), None
        w = want[r0:r0 + step]
        terms = np.abs(g - w) / (np.abs(w) + FLOOR)
        at = int(np.argmax(terms))
        return float(terms.flat[at]), np.unravel_index(at, terms.shape)

    best = (-1.0, None, 0)
    with ThreadPoolExecutor(_workers()) as ex:
        for r0, (v, at) in zip(range(0, len(got), step),
                               ex.map(block, range(0, len(got), step))):
            if v > best[0]:
                best = (v, at, r0)
            if v == float("inf"):
                return v, None
    v, at, r0 = best
    return v, (at[0] + r0, *at[1:]) if at is not None else None


def error(got, want):
    held = got.held if isinstance(got, Answer) else Held(got)
    hit = held.errors.get(id(want))
    if hit is None or hit[0] is not want:
        hit = held.errors[id(want)] = (want, _max_terms(held.array, want)[0])
    return hit[1]


def worst(got, want):
    v, at = _max_terms(_array(got), want)
    if at is None:
        return "a misshapen or non-finite answer"
    i, j, k, f = at
    return f"window ({i}, {j}), offset {k}, {FEATURES[f]}"


def cpu_cell(cell, config):
    return dict(cell, shape=CPU_SHAPE), "onehot"
