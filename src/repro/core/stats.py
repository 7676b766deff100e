"""Statistical epilogue for the association engine.

Everything here is pure ``jax.numpy`` so it can run inside the jitted scan
step on device, sharded along both the marker and the phenotype axis with no
collectives (all ops are elementwise over the ``(M, P)`` statistic tile).

Numerical notes
---------------
* Two-sided p-value of a t statistic with ``nu`` degrees of freedom is the
  regularized incomplete beta ``I_x(nu/2, 1/2)`` at ``x = nu / (nu + t^2)``.
* ``betainc`` underflows around ``p ~ 1e-35`` in float32.  GWAS hits routinely
  reach ``p < 1e-100``, so we always report ``-log10 p`` through a dedicated
  log-space branch:

  - tail (``t^2 > 6``): modified-Lentz continued fraction for
    ``I_x(a, b)`` evaluated as ``log I = a log x + b log1p(-x) - betaln(a,b)
    - log a + log(cf)``.  The CF converges for ``x < (a+1)/(a+b+2)``, which
    at ``t^2 > 6`` holds for every dof (see tests).
  - bulk (``t^2 <= 6``): the complement identity
    ``p = 1 - I_z(b, a)`` with ``z = t^2/(nu + t^2)`` computed directly —
    ``z`` is small and well conditioned in float32, unlike ``x = 1 - z``.

  Validated against ``scipy.stats.t.logsf`` across dof in {2..1e6} and
  t in [0, 1e3] in ``tests/test_stats.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import betainc, betaln, erfc, gammaincc, gammaln

__all__ = [
    "t_from_r",
    "chi2_from_r",
    "neglog10_p_from_t",
    "neglog10_p_from_r",
    "neglog10_sf_chi2",
    "t2_screen_threshold",
    "refine_neglog10p",
    "REFINE_WIDTH",
    "bh_qvalues",
    "genomic_control_lambda",
    "LOG10E",
]

LOG10E = 0.4342944819032518  # log10(e)

_CF_ITERS = 128     # fixed Lentz trips; ample inside the convergence region
_T2_SWITCH = 6.0    # t^2 above this -> log-space tail; below -> complement form
_FPMIN = 1e-30


def t_from_r(r: jax.Array, dof: jax.Array | float, *, eps: float = 1e-12) -> jax.Array:
    """Paper Eq. (3): ``T = R * sqrt(dof / (1 - R^2))``.

    ``dof`` is ``N - 2`` in the paper-faithful mode and ``N - 2 - q`` in the
    exact covariate mode.  ``1 - r^2`` is clamped at ``eps`` so monomorphic /
    perfectly-collinear columns produce large-but-finite statistics instead of
    inf (they are masked upstream anyway).
    """
    r = jnp.asarray(r)
    denom = jnp.maximum(1.0 - jnp.square(r), eps)
    return r * jnp.sqrt(jnp.asarray(dof, r.dtype) / denom)


def chi2_from_r(r: jax.Array, n_eff: jax.Array | float) -> jax.Array:
    """Large-sample score statistic ``N * r^2 ~ chi^2_1`` (used by the
    multivariate omnibus screen where per-trait dof corrections wash out)."""
    r = jnp.asarray(r)
    return jnp.asarray(n_eff, r.dtype) * jnp.square(r)


def _betacf(a: jax.Array, b: jax.Array, x: jax.Array) -> jax.Array:
    """Modified-Lentz continued fraction for the incomplete beta
    (Numerical Recipes betacf), elementwise, fixed ``_CF_ITERS`` trips.

    Converges for ``x < (a+1)/(a+b+2)``; callers clamp x into that region
    for lanes routed to the other branch.
    """
    qab, qap, qam = a + b, a + 1.0, a - 1.0
    c = jnp.ones_like(x)
    d = 1.0 - qab * x / qap
    d = jnp.where(jnp.abs(d) < _FPMIN, _FPMIN, d)
    d = 1.0 / d
    h = d

    def body(m, carry):
        c, d, h = carry
        mf = jnp.asarray(m, x.dtype) + 1.0
        m2 = 2.0 * mf
        # even step
        aa = mf * (b - mf) * x / ((qam + m2) * (a + m2))
        d = 1.0 + aa * d
        d = jnp.where(jnp.abs(d) < _FPMIN, _FPMIN, d)
        c = 1.0 + aa / c
        c = jnp.where(jnp.abs(c) < _FPMIN, _FPMIN, c)
        d = 1.0 / d
        h = h * d * c
        # odd step
        aa = -(a + mf) * (qab + mf) * x / ((a + m2) * (qap + m2))
        d = 1.0 + aa * d
        d = jnp.where(jnp.abs(d) < _FPMIN, _FPMIN, d)
        c = 1.0 + aa / c
        c = jnp.where(jnp.abs(c) < _FPMIN, _FPMIN, c)
        d = 1.0 / d
        h = h * d * c
        return c, d, h

    _, _, h = jax.lax.fori_loop(0, _CF_ITERS, body, (c, d, h))
    return h


_LGAMMA_HALF = 0.5723649429247001  # lgamma(1/2) = log(sqrt(pi))


def _betaln_half(a: jax.Array) -> jax.Array:
    """``betaln(a, 1/2)`` stable for huge ``a``.

    Direct lgamma differencing cancels catastrophically in f32 for
    ``a > ~1e4``; use ``Gamma(a+1/2)/Gamma(a) ~ sqrt(a)(1 - 1/(8a) +
    1/(128a^2))`` above a switch point (error O(a^-3)).
    """
    direct = betaln(a, jnp.asarray(0.5, a.dtype))
    inv = 1.0 / jnp.maximum(a, 1.0)
    asymptotic = _LGAMMA_HALF - 0.5 * jnp.log(jnp.maximum(a, 1.0)) - jnp.log1p(
        -0.125 * inv + (1.0 / 128.0) * inv * inv
    )
    return jnp.where(a > 200.0, asymptotic, direct)


def _log_p_tail(nu: jax.Array, t2: jax.Array) -> jax.Array:
    """``log I_x(nu/2, 1/2)`` at ``x = nu/(nu+t^2)`` — the two-sided t tail —
    with every term computed from the well-conditioned ratio ``t^2/nu``:

        a log x   = -a log1p(t^2/nu)
        b log(1-x)=  0.5 (log t^2 - log(nu + t^2))
    """
    a = nu * 0.5
    b = jnp.asarray(0.5, nu.dtype)
    x_cf = jnp.minimum(nu / (nu + t2), nu / (nu + _T2_SWITCH))
    cf = _betacf(a, b, x_cf)
    t2s = jnp.maximum(t2, _T2_SWITCH)  # bulk lanes are discarded by the caller
    log_x_term = -a * jnp.log1p(t2s / nu)
    log_1mx_term = 0.5 * (jnp.log(t2s) - jnp.log(nu + t2s))
    return (
        log_x_term
        + log_1mx_term
        - _betaln_half(a)
        - jnp.log(a)
        + jnp.log(jnp.maximum(cf, _FPMIN))
    )


_SQRT_HALF = 0.7071067811865476
_INV_SQRT_2PI = 0.3989422804014327
_NU_BETAINC = 4096.0   # below this dof the f32 betainc complement is accurate
_T2_ERFC_MAX = 144.0   # erfc underflows in f32 past |t| ~ 12


def neglog10_p_from_t(t: jax.Array, dof: jax.Array | float) -> jax.Array:
    """Two-sided ``-log10 p`` for a t statistic, stable to ``p ~ 1e-10000``.

    Three lanes, selected elementwise by an adaptive switch
    ``t2* = clip(nu/2000, 6, 144)`` (chosen from a measured f32 error map;
    see EXPERIMENTS.md):

      * tail (``t^2 > t2*``): log-space continued fraction — never
        underflows, f32 cancellation error <= ~1e-4 rel on -log10 p for
        ``nu <= 2e6`` (i.e. cohorts up to ~2M samples; beyond that the tail
        lane degrades gracefully to ~1e-3 — documented envelope);
      * bulk, ``nu <= 4096``: complement identity ``p = 1 - I_z(1/2, nu/2)``
        on the well-conditioned small variable ``z = t^2/(nu+t^2)``;
      * bulk, ``nu > 4096``: Edgeworth-corrected normal
        ``P(T>t) = Q(t) + (t^3+t) phi(t)/(4 nu) + O(nu^-2)`` — jax's f32
        ``betainc`` loses accuracy for ``a = nu/2 > ~1e4``.
    """
    t = jnp.asarray(t, jnp.float32)
    nu = jnp.asarray(dof, jnp.float32) * jnp.ones_like(t)
    t2 = jnp.square(t)
    z = t2 / (nu + t2)
    a = nu * 0.5
    b = jnp.asarray(0.5, jnp.float32)
    t2_switch = jnp.clip(nu / 2000.0, _T2_SWITCH, _T2_ERFC_MAX)

    log_p_tail = _log_p_tail(nu, jnp.maximum(t2, t2_switch))

    p_beta = 1.0 - betainc(b, a, jnp.clip(z, 0.0, 1.0))
    abs_t = jnp.abs(t)
    q_norm = 0.5 * erfc(abs_t * _SQRT_HALF)
    phi = _INV_SQRT_2PI * jnp.exp(-0.5 * jnp.minimum(t2, 160.0))
    p_norm = 2.0 * (q_norm + (abs_t * t2 + abs_t) * phi / (4.0 * nu))
    p_bulk = jnp.where(nu > _NU_BETAINC, p_norm, p_beta)
    log_p_bulk = jnp.log(jnp.clip(p_bulk, 1e-38, 1.0))

    log_p = jnp.where(t2 > t2_switch, log_p_tail, log_p_bulk)
    return jnp.maximum(-LOG10E * log_p, 0.0)


def neglog10_p_from_r(r: jax.Array, dof: jax.Array | float) -> jax.Array:
    """Fused convenience path ``r -> t -> -log10 p``."""
    return neglog10_p_from_t(t_from_r(r, dof), dof)


# ------------------------------------------------- sparse-epilogue screening
#
# The monotonicity contract (DESIGN.md §13): for fixed dof, the exact
# two-sided tail is strictly decreasing in t^2, so -log10 p is strictly
# increasing in t^2.  ``neglog10_p_from_t`` evaluates that function in f32
# with bounded error (<= ~5e-3 relative, tests/test_stats.py) and bounded
# local non-monotonic jitter (<= 1e-3, ``test_neglog10_p_deep_tail_monotone``).
# Inverting the hit threshold through the device function itself therefore
# yields a t^2 bound that — once padded by a margin dwarfing both error
# terms — soundly *underestimates* the true boundary: every lane the device
# would report as a hit passes the screen, and only near-threshold misses
# are screened in spuriously (the exact CF then rejects them).

_T2_SCREEN_MAX = 1e37  # f32-finite cap for the bracket search


@functools.lru_cache(maxsize=1024)
def t2_screen_threshold(threshold_nlp: float, dof: float) -> float | None:
    """Invert the hit threshold to a conservative per-dof t^2 screen bound.

    Returns ``t2*`` such that ``neglog10_p_from_t(t, dof) >= threshold_nlp``
    implies ``t^2 >= t2*`` — the admission test of the sparse p-value
    epilogue.  Host-side bisection on the f32 device function (so the bound
    is consistent with the code that later refines survivors), against a
    reduced target ``threshold - (0.05 + 0.02*threshold)`` whose margin
    covers both the f32 evaluation error (<= ~5e-3 relative, twice — once
    at the boundary probe, once on the screened lane) and the
    non-monotonic jitter.
    Cached per (threshold, dof): one inversion per scan, reused by every
    grid cell.

    ``None`` means no useful bound exists (threshold at or below the
    margin floor): callers must fall back to the dense epilogue.
    """
    threshold_nlp = float(threshold_nlp)
    dof = float(dof)
    target = threshold_nlp - (0.05 + 0.02 * threshold_nlp)
    if not (target > 0.0) or not (dof > 0.0):
        return None

    def gwas_screen_probe(t2):
        return neglog10_p_from_t(jnp.sqrt(t2), dof)

    f = jax.jit(gwas_screen_probe)

    def nlp32(t2: float) -> float:
        return float(f(jnp.float32(t2)))

    hi = 1.0
    while nlp32(hi) < target:
        hi *= 4.0
        if hi > _T2_SCREEN_MAX:
            # Even the largest representable statistic stays below the
            # target, so no lane can ever reach the threshold: a screen at
            # the cap soundly rejects everything.
            return float(_T2_SCREEN_MAX)
    lo = 0.0
    for _ in range(96):
        mid = 0.5 * (lo + hi)
        if mid <= lo or mid >= hi:
            break
        if nlp32(mid) < target:
            lo = mid
        else:
            hi = mid
    # ``lo`` is the largest probe still below the reduced target; one ulp
    # down (in f32, the comparison precision on device) for strictness.
    return float(np.nextafter(np.float32(lo), np.float32(0.0)))


# Canonical chunk width for refining hit buffers (DESIGN.md §13).  Every
# hit-valued refine — compact buffer, overflow fallback, dense audit,
# tile reconstruction — evaluates in fixed (REFINE_WIDTH,) chunks so the
# emitted bits cannot depend on the configured buffer capacity.  A full
# SIMD multiple, so no scalar remainder lanes exist whose position could
# change a bit.
REFINE_WIDTH = 64


@functools.lru_cache(maxsize=None)
def _refine_exe(length: int, dof: float):
    """One cached executable per (shape, dof).  XLA's codegen for the CF
    loop is context-sensitive — the same values evaluated at a different
    shape or inside a differently-fused program can differ in the last
    f32 bit — so every emitted -log10 p must come out of *one* compiled
    program.  This cache is that program."""
    def gwas_refine(t):
        return neglog10_p_from_t(t, dof)

    return jax.jit(gwas_refine)


class RefineTally:
    """Counts one consumer's refine work: executable ``launches``, the
    ``lanes`` they evaluated (padding included), and the launches whose
    output landed on a device other than ``home`` (``foreign``).  ``home``
    None counts none as foreign."""

    __slots__ = ("home", "launches", "lanes", "foreign")

    def __init__(self, home=None):
        self.home = home
        self.launches = 0
        self.lanes = 0
        self.foreign = 0

    def count(self, out: jax.Array) -> None:
        self.launches += 1
        self.lanes += int(out.shape[0])
        if self.home is not None and self.home not in out.devices():
            self.foreign += 1


def refine_neglog10p(
    t_values: np.ndarray, dof: float, *, width: int | None = None,
    tally: RefineTally | None = None,
) -> np.ndarray:
    """Canonical exact-tail refine (DESIGN.md §13).

    Evaluates the exact 128-trip CF on a 1-D t buffer through the cached
    per-(shape, dof) executable.  With ``width``, the buffer is zero-padded
    and evaluated in fixed ``(width,)`` chunks; hit-valued callers always
    pass ``width=REFINE_WIDTH``, so the sparse compact path, the overflow
    fallback, the dense audit mode, and the full-tile reconstruction all
    feed slot-identical chunks to one executable and produce bit-identical
    values for the same t.  Padding lanes (t=0) map to nlp=0 and are
    sliced off.  ``tally`` counts every launch.
    """
    flat = np.ascontiguousarray(np.asarray(t_values, np.float32).ravel())
    dof = float(dof)

    def launch(exe, x: np.ndarray) -> np.ndarray:
        out = exe(jnp.asarray(x))
        if tally is not None:
            tally.count(out)
        return np.asarray(out)

    if width is None:
        return launch(_refine_exe(int(flat.shape[0]), dof), flat)
    width = int(width)
    k = int(flat.shape[0])
    n_chunks = max(1, -(-k // width))
    buf = np.zeros(n_chunks * width, np.float32)
    buf[:k] = flat
    exe = _refine_exe(width, dof)
    out = np.concatenate(
        [launch(exe, buf[i * width:(i + 1) * width]) for i in range(n_chunks)]
    )
    return out[:k]


def _log_gammaincc_cf(a: jax.Array, z: jax.Array) -> jax.Array:
    """``log( Gamma(a, z) / Gamma(a) )`` via the NR ``gcf`` continued
    fraction, valid (and fast) for ``z > a + 1``.  Log-space: never
    underflows."""
    b0 = z + 1.0 - a
    c = jnp.full_like(z, 1.0 / _FPMIN)
    d = 1.0 / jnp.where(jnp.abs(b0) < _FPMIN, _FPMIN, b0)
    h = d

    def body(i, carry):
        c, d, h, b0 = carry
        i_f = jnp.asarray(i, z.dtype) + 1.0
        an = -i_f * (i_f - a)
        b0 = b0 + 2.0
        d = an * d + b0
        d = jnp.where(jnp.abs(d) < _FPMIN, _FPMIN, d)
        c = b0 + an / c
        c = jnp.where(jnp.abs(c) < _FPMIN, _FPMIN, c)
        d = 1.0 / d
        h = h * d * c
        return c, d, h, b0

    _, _, h, _ = jax.lax.fori_loop(0, _CF_ITERS, body, (c, d, h, b0))
    return -z + a * jnp.log(jnp.maximum(z, 1e-38)) - gammaln(a) + jnp.log(
        jnp.maximum(h, _FPMIN)
    )


def neglog10_sf_chi2(stat: jax.Array, k: jax.Array | float) -> jax.Array:
    """``-log10 P(chi^2_k >= stat)``, stable into the deep tail.

    Bulk lanes (sf not near underflow) use ``gammaincc`` directly; tail lanes
    (``z > a+1`` and sf tiny) use the log-space ``gcf`` continued fraction.
    """
    s = jnp.asarray(stat, jnp.float32)
    a = jnp.asarray(k, jnp.float32) * 0.5 * jnp.ones_like(s)
    half = s * 0.5
    direct = gammaincc(a, jnp.maximum(half, 0.0))
    log_direct = jnp.log(jnp.maximum(direct, 1e-38))
    z_cf = jnp.maximum(half, a + 1.001)  # clamp unused lanes into validity
    log_tail = _log_gammaincc_cf(a, z_cf)
    use_tail = (half > a + 1.0) & (direct < 1e-6)
    log_sf = jnp.where(use_tail, log_tail, log_direct)
    return jnp.maximum(-LOG10E * log_sf, 0.0)


def bh_qvalues(neglog10p: jax.Array) -> jax.Array:
    """Benjamini-Hochberg q-values from a flat vector of ``-log10 p``.

    Monotone step-up in log space: sort ascending by p (descending by
    ``-log10 p``), apply ``q_i = min_{j >= i} p_j * m / j``.
    Returns q as ``-log10 q`` in the original order.
    """
    nlp = jnp.ravel(neglog10p)
    m = nlp.shape[0]
    order = jnp.argsort(-nlp)  # most significant first
    nlp_sorted = nlp[order]
    ranks = jnp.arange(1, m + 1, dtype=nlp.dtype)
    # -log10(p * m / rank) = nlp - log10(m) + log10(rank)
    nlq_raw = nlp_sorted - jnp.log10(jnp.asarray(m, nlp.dtype)) + jnp.log10(ranks)
    # enforce monotone non-increasing significance via reverse cummax
    nlq_sorted = jax.lax.cummax(nlq_raw[::-1])[::-1]
    nlq_sorted = jnp.maximum(nlq_sorted, 0.0)
    inv = jnp.argsort(order)
    return nlq_sorted[inv].reshape(neglog10p.shape)


def genomic_control_lambda(t_stats: jax.Array) -> jax.Array:
    """Genomic-control lambda: median(t^2) / qchisq(0.5, 1).

    ``qchisq(0.5, 1) = 0.45493642``.  Values near 1 indicate a calibrated
    scan; inflation (relatedness/stratification) pushes it above 1.  Used by
    tests to check calibration on null panels.
    """
    chi2 = jnp.square(jnp.asarray(t_stats, jnp.float32))
    return jnp.median(chi2) / 0.45493642311957184
