"""Ball algebra for the augmented-space MEB that underlies the l2-SVM.

A ``Ball`` is the streaming state of StreamSVM: the center of the minimum
enclosing ball in the augmented feature space ``phi~(z_n) = [y_n x_n ;
C^{-1/2} e_n]`` is ``[w ; sigma]`` where ``sigma`` is the slack block. Because
every example contributes a fresh orthogonal slack direction and is seen only
once, ``sigma`` never needs to be stored: its squared norm ``xi2`` suffices
for every distance computation the algorithm performs (paper, Sec. 4.1).

All functions are branch-free (jnp.where) so they jit/vmap/scan cleanly.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

_EPS = 1e-12


class Ball(NamedTuple):
    """Streaming MEB state == StreamSVM classifier state.

    w:   (D,) feature block of the ball center == SVM weight vector.
    r:   () radius.
    xi2: () squared norm of the slack block of the center.
    m:   () int32 — number of core vectors absorbed (paper's M).
    """

    w: jax.Array
    r: jax.Array
    xi2: jax.Array
    m: jax.Array

    @property
    def dim(self) -> int:
        return self.w.shape[-1]


def make_ball(w, r=0.0, xi2=0.0, m=1) -> Ball:
    w = jnp.asarray(w)
    dt = w.dtype
    return Ball(
        w=w,
        r=jnp.asarray(r, dt),
        xi2=jnp.asarray(xi2, dt),
        m=jnp.asarray(m, jnp.int32),
    )


def center_distance(b1: Ball, b2: Ball) -> jax.Array:
    """Distance between two ball centers in the augmented space.

    Valid when the two balls were built from disjoint example sets (always
    true for stream shards): their slack blocks are orthogonal, so
    ``|c1-c2|^2 = |w1-w2|^2 + xi1^2 + xi2^2``.
    """
    d2 = jnp.sum((b1.w - b2.w) ** 2) + b1.xi2 + b2.xi2
    return jnp.sqrt(jnp.maximum(d2, 0.0))


def point_distance(ball: Ball, yx: jax.Array, c_inv) -> jax.Array:
    """Distance from the ball center to augmented point [y x ; C^{-1/2} e_new].

    ``yx`` is the label-signed feature row y*x; ``c_inv`` is 1/C. The point's
    slack direction is fresh, hence the ``+ xi2 + 1/C`` closed form
    (Algorithm 1, line 5).
    """
    d2 = jnp.sum((ball.w - yx) ** 2) + ball.xi2 + c_inv
    return jnp.sqrt(jnp.maximum(d2, _EPS))


def enclose_point(ball: Ball, yx: jax.Array, c_inv, *, variant: str = "exact") -> Ball:
    """Algorithm 1 inner update, unconditionally applied (branchless).

    Returns the smallest ball enclosing ``ball`` and the augmented point.
    Caller selects with the ``d >= r`` predicate. ``variant``:
      - "exact": slack recursion xi2 <- xi2 (1-s)^2 + s^2 / C (exact
        bookkeeping of the augmented center; see DESIGN.md erratum note).
      - "paper-listing": verbatim line 9, xi2 <- xi2 (1-s)^2 + s^2.
    """
    d = point_distance(ball, yx, c_inv)
    s = 0.5 * (1.0 - ball.r / d)  # step toward the new point
    w = ball.w + s * (yx - ball.w)
    r = ball.r + 0.5 * (d - ball.r)
    slack_gain = c_inv if variant == "exact" else jnp.asarray(1.0, ball.xi2.dtype)
    xi2 = ball.xi2 * (1.0 - s) ** 2 + (s**2) * slack_gain
    return Ball(w=w, r=r, xi2=xi2, m=ball.m + 1)


def merge_balls(b1: Ball, b2: Ball) -> Ball:
    """Smallest ball enclosing two balls built from disjoint example sets.

    Exact in the augmented space (slack blocks orthogonal). This is the
    paper's Sec 4.3 multi-ball merge; we use it as the cross-shard collective
    combiner. Branch-free: handles mutual containment and coincident centers.
    """
    dist = center_distance(b1, b2)
    safe = jnp.maximum(dist, _EPS)

    one_in_two = dist + b1.r <= b2.r
    two_in_one = dist + b2.r <= b1.r

    r_join = 0.5 * (b1.r + b2.r + dist)
    t = jnp.clip((r_join - b1.r) / safe, 0.0, 1.0)
    w_join = b1.w + t * (b2.w - b1.w)
    xi2_join = (1.0 - t) ** 2 * b1.xi2 + t**2 * b2.xi2

    w = jnp.where(one_in_two, b2.w, jnp.where(two_in_one, b1.w, w_join))
    r = jnp.where(one_in_two, b2.r, jnp.where(two_in_one, b1.r, r_join))
    xi2 = jnp.where(one_in_two, b2.xi2, jnp.where(two_in_one, b1.xi2, xi2_join))
    return Ball(w=w, r=r, xi2=xi2, m=b1.m + b2.m)


def _is_kernel_bank(bank) -> bool:
    """True for KernelBank-shaped pytrees (core-set buffers present)."""
    return hasattr(bank, "coef") and hasattr(bank, "points")


def _require_kind(fn_name: str, banks, *, want_kernel: bool) -> None:
    """Refuse linear/kernel bank mixing with a ValueError naming both sides.

    A Ball center lives in the explicit feature space; a KernelBank center
    is a coefficient expansion over stored core-set points. Their merge
    algebras are NOT interchangeable — silently treating one as the other
    produces garbage scores, so every fold/merge entry point checks first.
    """
    names = [type(b).__name__ for b in banks]
    bad = [n for b, n in zip(banks, names) if _is_kernel_bank(b) != want_kernel]
    if bad:
        expected = "KernelBank" if want_kernel else "linear Ball"
        other = (
            "linear banks merge via merge_banks/fold_banks/stack_banks"
            if want_kernel
            else "kernelized banks merge via merge_kernel_banks/"
            "fold_kernel_banks/stack_kernel_banks (kernel=..., gamma=...)"
        )
        raise ValueError(
            f"{fn_name} operates on {expected} banks; got {names} — "
            f"mixing linear and kernelized banks has no exact merge; {other}"
        )


def _pair_gram(P1, P2, kernel: str, gamma):
    """(B, S1, S2) kernel matrix between two (B, S, D) core-set buffers."""
    P1 = P1.astype(jnp.float32)
    P2 = P2.astype(jnp.float32)
    acc = jnp.einsum("bsd,btd->bst", P1, P2, preferred_element_type=jnp.float32)
    if kernel == "rbf":
        n1 = jnp.sum(P1 * P1, axis=-1)
        n2 = jnp.sum(P2 * P2, axis=-1)
        return jnp.exp(
            -jnp.asarray(gamma, jnp.float32)
            * jnp.maximum(n1[:, :, None] + n2[:, None, :] - 2.0 * acc, 0.0)
        )
    return acc


def merge_kernel_banks(b1, b2, *, kernel: str, gamma=1.0,
                       eviction: str = "smallest-coef",
                       return_dropped: bool = False):
    """Sec-4.3 merge of two kernelized banks built from disjoint example sets.

    The kernel-space twin of ``merge_banks``: both arguments are
    ``KernelBank``s of identical (B, S) shape whose centers live in the same
    RKHS, c_i = sum_s coef_i[s] phi(p_i[s]) plus an orthogonal slack block of
    squared norm xi2_i. The center distance needs one cross-Gram
    contraction,

        |c1 - c2|^2 = q1 + q2 - 2 sum_{s,t} coef1[s] coef2[t] k(p1s, p2t)
                      + xi1 + xi2,

    and then the EXACT ``merge_balls`` algebra applies unchanged: r_join =
    (r1 + r2 + dist) / 2, t = clip((r_join - r1)/dist, 0, 1), with the
    merged center c = (1-t) c1 + t c2 represented on the CONCATENATED
    (B, 2S) buffer as [(1-t) coef1 ; t coef2] and q_join following the same
    interpolation ((1-t)^2 q1 + 2 t (1-t) cross + t^2 q2). Containment and
    empty-bank cases (m == 0 — a fully padded stream shard — is an exact
    identity) collapse onto t in {0, 1}, keeping everything branch-free.

    The 2S-slot buffer is then compressed back to S slots — the
    coreset-of-coresets step ("On Coresets for SVMs", PAPERS.md) — keeping
    the top-S slots under the SAME ``eviction`` policy the fit used:
    "smallest-coef" keeps the largest |coef|, "farthest-point" keeps the
    slots farthest from the merged center. Free slots (coef 0 / score -inf)
    are always dropped first, so the merge is EXACT (no mass lost) whenever
    the live slots of both inputs fit in S; beyond that it is lossy in the
    same sense as the fit's eviction — q keeps the dense-recursion value
    while the buffer approximates the center. Numpy oracle:
    ``kernels.ref.merge_kernel_banks_ref``; property/parity suites:
    tests/test_kernel_merge.py.

    ``return_dropped=True`` additionally returns the (B,) |coef| mass the
    2S->S cut discarded per model — the re-compression loss audit. It is
    computed from the NOT-kept slots directly (a scatter of the kept index
    set), so it is exactly 0.0 whenever every dropped slot was free
    (coef == 0), with no f32 mass-difference round-off.
    """
    from .kernel_bank import KernelBank  # lazy: module cycle

    _require_kind("merge_kernel_banks", (b1, b2), want_kernel=True)
    if b1.coef.shape != b2.coef.shape:
        raise ValueError(
            f"merge_kernel_banks needs identically-shaped banks: got "
            f"coef {b1.coef.shape} vs {b2.coef.shape}"
        )
    if eviction not in ("smallest-coef", "farthest-point"):
        raise ValueError(
            f"unknown eviction {eviction!r}; expected 'smallest-coef' or "
            "'farthest-point'"
        )
    s_size = b1.coef.shape[1]
    c1 = b1.coef.astype(jnp.float32)
    c2 = b2.coef.astype(jnp.float32)
    k12 = _pair_gram(b1.points, b2.points, kernel, gamma)
    cross = jnp.einsum("bs,bst,bt->b", c1, k12, c2)

    d2 = b1.q + b2.q - 2.0 * cross + b1.xi2 + b2.xi2
    dist = jnp.sqrt(jnp.maximum(d2, 0.0))
    safe = jnp.maximum(dist, _EPS)
    one_in_two = dist + b1.r <= b2.r
    two_in_one = dist + b2.r <= b1.r
    empty1 = b1.m == 0
    empty2 = b2.m == 0

    r_join = 0.5 * (b1.r + b2.r + dist)
    t = jnp.clip((r_join - b1.r) / safe, 0.0, 1.0)
    # Containment / empty-identity collapse onto the interpolation weight
    # (t = 1 keeps bank 2's center exactly, t = 0 bank 1's) and the radius.
    t = jnp.where(one_in_two, 1.0, jnp.where(two_in_one, 0.0, t))
    t = jnp.where(empty1, 1.0, jnp.where(empty2, 0.0, t))
    r = jnp.where(one_in_two, b2.r, jnp.where(two_in_one, b1.r, r_join))
    r = jnp.where(empty1, b2.r, jnp.where(empty2, b1.r, r))

    q = (1.0 - t) ** 2 * b1.q + 2.0 * t * (1.0 - t) * cross + t**2 * b2.q
    xi2 = (1.0 - t) ** 2 * b1.xi2 + t**2 * b2.xi2
    m = b1.m + b2.m

    idx_c = jnp.concatenate([b1.idx, b2.idx], axis=1)  # (B, 2S)
    coef_c = jnp.concatenate(
        [(1.0 - t)[:, None] * c1, t[:, None] * c2], axis=1
    )
    pts_c = jnp.concatenate(
        [b1.points.astype(jnp.float32), b2.points.astype(jnp.float32)], axis=1
    )

    if eviction == "farthest-point":
        kcc = _pair_gram(pts_c, pts_c, kernel, gamma)
        gs = jnp.einsum(
            "bst,bt->bs", kcc, coef_c, preferred_element_type=jnp.float32
        )
        kdiag = jnp.diagonal(kcc, axis1=1, axis2=2)
        score = jnp.where(
            idx_c >= 0,
            q[:, None] - 2.0 * jnp.sign(coef_c) * gs + kdiag,
            -jnp.inf,
        )  # keep the slots FARTHEST from the merged center
    else:
        score = jnp.where(idx_c >= 0, jnp.abs(coef_c), -jnp.inf)
    _, keep = jax.lax.top_k(score, s_size)  # (B, S), ties -> lowest index
    merged = KernelBank(
        idx=jnp.take_along_axis(idx_c, keep, axis=1),
        coef=jnp.take_along_axis(coef_c, keep, axis=1),
        points=jnp.take_along_axis(pts_c, keep[..., None], axis=1),
        q=q, r=r, xi2=xi2, m=m,
    )
    if not return_dropped:
        return merged
    bsz = coef_c.shape[0]
    kept = jnp.zeros(coef_c.shape, bool).at[
        jnp.arange(bsz)[:, None], keep
    ].set(True)
    dropped = jnp.sum(jnp.where(kept, 0.0, jnp.abs(coef_c)), axis=1)
    return merged, dropped


def stack_kernel_banks(banks):
    """Stack an iterable of same-shape KernelBanks on a NEW leading axis.

    The kernelized ``stack_banks``: K banks of coef shape (B, S) become one
    stacked KernelBank with coef (K, B, S) — the layout the live loop
    checkpoints its K rotating kernel sub-banks in, and the form
    ``fold_kernel_banks`` unstacks to fold.
    """
    banks = list(banks)
    if not banks:
        raise ValueError(
            "stack_kernel_banks needs at least one bank; got an empty sequence"
        )
    _require_kind("stack_kernel_banks", banks, want_kernel=True)
    return jax.tree.map(lambda *xs: jnp.stack(xs), *banks)


def fold_kernel_banks(banks, *, kernel: str, gamma=1.0,
                      eviction: str = "smallest-coef",
                      live=None, return_dropped: bool = False):
    """Left fold of same-shape KernelBanks, in order.

    The kernelized ``fold_banks``: shard count is static and small, so the
    fold is a plain python loop of ``merge_kernel_banks`` (callers pass
    shards oldest/leftmost first — the order ``fit_kernel_bank_sharded``
    gathers them in, and the birth order the live loop folds its sub-bank
    slots in). ``banks`` is either a python sequence of (B, S) banks or a
    stacked KernelBank from ``stack_kernel_banks`` (coef (K, B, S)).

    ``live``: optional (K,) bool mask; dead entries are skipped EXACTLY —
    the fold of the live entries is bit-identical to folding only those
    entries, because dead slots never enter a merge at all (the dead-slot
    exactness contract of the linear ``fold_merge``). At least one entry
    must be live. ``return_dropped=True`` additionally returns the summed
    (B,) dropped-|coef| mass over every 2S->S cut the fold performed
    (see ``merge_kernel_banks``); a single live bank passes through with
    exactly zero dropped mass.
    """
    if _is_kernel_bank(banks) and getattr(banks.coef, "ndim", 0) == 3:
        k = banks.coef.shape[0]
        banks = [jax.tree.map(lambda x, i=i: x[i], banks) for i in range(k)]
    else:
        banks = list(banks)
    if not banks:
        raise ValueError(
            "fold_kernel_banks needs at least one bank; got an empty sequence"
        )
    _require_kind("fold_kernel_banks", banks, want_kernel=True)
    if live is not None:
        import numpy as np

        mask = np.asarray(live)
        if mask.shape != (len(banks),):
            raise ValueError(
                f"live mask shape {mask.shape} does not match the "
                f"{len(banks)} banks being folded"
            )
        banks = [b for b, alive in zip(banks, mask) if alive]
        if not banks:
            raise ValueError(
                "fold_kernel_banks needs at least one LIVE bank; the live "
                "mask marked every entry dead"
            )
    acc = banks[0]
    dropped = jnp.zeros(acc.coef.shape[0], jnp.float32)
    for nxt in banks[1:]:
        acc, d = merge_kernel_banks(
            acc, nxt, kernel=kernel, gamma=gamma, eviction=eviction,
            return_dropped=True,
        )
        dropped = dropped + d
    if return_dropped:
        return acc, dropped
    return acc


def nonfinite_rows(bank) -> jax.Array:
    """(B,) bool: model rows whose FLOAT state contains NaN/Inf.

    Works on any (B, ...)-leading bank pytree — a linear ``Ball`` (w, r,
    xi2 checked; integer m skipped) or a ``KernelBank`` (coef, points, q,
    r, xi2 checked; integer idx/m skipped). This is the live loop's
    publish guard: a fold with any poisoned row must never be hot-swapped
    into a server, because a single NaN coordinate turns every score of
    that model row into NaN.
    """
    leaves = [
        jnp.asarray(leaf)
        for leaf in jax.tree.leaves(bank)
        if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating)
    ]
    if not leaves:
        raise ValueError(
            f"nonfinite_rows needs at least one float leaf: got {bank!r}"
        )
    b = leaves[0].shape[0]
    bad = jnp.zeros((b,), bool)
    for leaf in leaves:
        if leaf.shape[:1] != (b,):
            raise ValueError(
                "nonfinite_rows needs every float leaf stacked on the same "
                f"leading B axis: got shapes {[l.shape for l in leaves]}"
            )
        bad = bad | jnp.any(~jnp.isfinite(leaf.reshape(b, -1)), axis=1)
    return bad


def merge_banks(b1: Ball, b2: Ball) -> Ball:
    """Sec-4.3 merge vmapped over a leading bank axis: B models at once.

    Both arguments are Balls stacked on a leading B axis (w: (B, D), scalars
    (B,)); model b of the result merges model b of each bank — the lanes
    never interact.
    """
    _require_kind("merge_banks", (b1, b2), want_kernel=False)
    return jax.vmap(merge_balls)(b1, b2)


def stack_banks(banks) -> Ball:
    """Stack an iterable of same-shape Ball banks on a NEW leading axis.

    K banks of shape (B, D) become one stacked Ball with w: (K, B, D) —
    the layout ``fold_merge`` folds bank-wise and the live loop checkpoints
    (repro.live keeps its K rotating sub-banks exactly like this).
    """
    banks = list(banks)
    if not banks:
        raise ValueError("stack_banks needs at least one bank; got an empty sequence")
    _require_kind("stack_banks", banks, want_kernel=False)
    return jax.tree.map(lambda *xs: jnp.stack(xs), *banks)


def fold_banks(banks, live=None) -> Ball:
    """Sec-4.3 fold of a python sequence of same-shape banks, in order.

    The sub-bank fold helper behind the live loop's drift repair: K rotating
    sub-banks — each a (B, D) stacked Ball trained over its own span of the
    stream, hence disjoint example sets — fold left-to-right (callers pass
    oldest first) into ONE serving bank via the bank-vectorized merge.
    Equivalent to ``fold_merge(stack_banks(banks))``; a single bank passes
    through untouched. ``live``: optional (K,) bool mask forwarded to
    ``fold_merge`` — dead entries are skipped exactly, matching
    ``fold_kernel_banks(..., live=)``.
    """
    banks = list(banks)
    if not banks:
        raise ValueError("fold_banks needs at least one bank; got an empty sequence")
    _require_kind("fold_banks", banks, want_kernel=False)
    if live is not None:
        return fold_merge(stack_banks(banks), live=jnp.asarray(live))
    if len(banks) == 1:
        return banks[0]
    return fold_merge(stack_banks(banks))


@jax.jit
def fold_merge(balls: Ball, live: jax.Array | None = None) -> Ball:
    """Deterministic left fold of a stacked Ball pytree (leading axis).

    Accepts stacked single balls (w: (S, D)) or stacked BANKS (w: (S, B, D))
    — the bank case folds every model lane independently via the vmapped
    Sec-4.3 merge, which is how fit_bank_sharded combines per-shard banks.

    ``live``: optional (S,) bool mask; entries with ``live[i] == False`` are
    skipped exactly (the accumulator passes through), which is how fully
    padded shards — shards whose whole contiguous range is remainder padding
    — are excluded from the fold. The fold starts at the FIRST live entry
    (so a dead entry 0 cannot contaminate the result); at least one entry
    must be live.

    Compiled once per shape: run op by op, the loop body (a fresh closure
    each call) is traced, lowered and compiled anew on every call. Every
    caller, the sharded fit's eager fold and the per-range folds it is
    checked against alike, runs this one program.
    """
    n = balls.w.shape[0]
    merge = merge_balls if balls.w.ndim == 2 else merge_banks

    def take(i):
        return jax.tree.map(lambda x: x[i], balls)

    if live is None:
        def body(i, acc):
            return merge(acc, take(i))

        return jax.lax.fori_loop(1, n, body, take(0))

    i0 = jnp.argmax(live)  # index of the first live entry

    def body(i, acc):
        new = merge(acc, take(i))
        use = jnp.logical_and(live[i], i != i0)  # skip dead; don't self-merge
        return jax.tree.map(lambda a, b: jnp.where(use, a, b), new, acc)

    return jax.lax.fori_loop(0, n, body, take(i0))
