"""Cartesian Taylor multipole operators for the Laplace kernel G(r) = 1/|r|.

This is the numerical heart of the FMM reproduced from the paper (exaFMM's
Laplace Cartesian kernel at order P=4).  A multipole expansion about center c
is the coefficient vector

    M_k = sum_i q_i (x_i - c)^k / k!          for multi-indices |k| <= P-1,

a local expansion is  phi(y) = sum_j L_j (y - c)^j / j!.

The M2L translation needs derivative tensors D_k G up to order 2(P-1).  We
build them with *nested jax.jacfwd* — exact AD instead of hand-derived
recurrences — and gather the unique multi-index entries.  All operators are
pure JAX functions, vmap-able and differentiable.
"""
from __future__ import annotations

import math
from functools import cached_property, lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "multi_indices", "num_coeffs", "p2m", "m2m", "m2l", "l2l", "l2p", "m2p",
    "p2p", "MultipoleOperators",
]


def multi_indices(max_order: int) -> np.ndarray:
    """All 3D multi-indices k with |k| <= max_order, ordered by order then lex."""
    out = []
    for n in range(max_order + 1):
        for kx in range(n, -1, -1):
            for ky in range(n - kx, -1, -1):
                out.append((kx, ky, n - kx - ky))
    return np.array(out, dtype=np.int32)


def num_coeffs(p: int) -> int:
    """Number of coefficients for expansion order p (indices |k| <= p-1)."""
    return (p * (p + 1) * (p + 2)) // 6


def _factorial_prod(idx: np.ndarray) -> np.ndarray:
    f = np.array([math.factorial(i) for i in range(idx.max() + 1)], dtype=np.float64)
    return f[idx[:, 0]] * f[idx[:, 1]] * f[idx[:, 2]]


@lru_cache(maxsize=None)
def _tables(p: int):
    """Precomputed integer/float tables for order-p operators (NumPy, host)."""
    K = multi_indices(p - 1)            # (nk, 3) expansion indices
    E = multi_indices(2 * (p - 1))      # (ne, 3) extended (for M2L derivatives)
    nk, ne = len(K), len(E)
    lookup = {tuple(k): i for i, k in enumerate(E)}
    fact_K = _factorial_prod(K)                       # k!
    order_K = K.sum(axis=1)

    # translation tables: T[j, k] uses monomial at (j - k) (M2M) or (k - j) (L2L)
    m2m_idx = np.zeros((nk, nk), dtype=np.int32)
    m2m_valid = np.zeros((nk, nk), dtype=bool)
    l2l_idx = np.zeros((nk, nk), dtype=np.int32)
    l2l_valid = np.zeros((nk, nk), dtype=bool)
    m2l_idx = np.zeros((nk, nk), dtype=np.int32)      # index of (j + k) in E
    for j in range(nk):
        for k in range(nk):
            d = K[j] - K[k]
            if (d >= 0).all():
                m2m_idx[j, k] = lookup[tuple(d)]
                m2m_valid[j, k] = True
            d = K[k] - K[j]
            if (d >= 0).all():
                l2l_idx[j, k] = lookup[tuple(d)]
                l2l_valid[j, k] = True
            m2l_idx[j, k] = lookup[tuple(K[j] + K[k])]

    # inverse factorial of the *monomial* index per table entry
    fact_E = _factorial_prod(E)
    inv_fact_E = 1.0 / fact_E
    sign_K = np.where(order_K % 2 == 0, 1.0, -1.0)    # (-1)^|k|

    # gather map: for each extended index of order n, the flat position inside
    # the order-n full derivative tensor (shape 3^n), via repeated axes (0/1/2)
    per_order_pos = []
    for n in range(2 * (p - 1) + 1):
        rows = E[E.sum(axis=1) == n]
        pos = []
        for kx, ky, kz in rows:
            digits = [0] * kx + [1] * ky + [2] * kz
            flat = 0
            for dgt in digits:
                flat = flat * 3 + dgt
            pos.append(flat)
        per_order_pos.append(np.array(pos, dtype=np.int32))
    return dict(
        K=K, E=E, nk=nk, ne=ne,
        inv_fact_K=(1.0 / fact_K), sign_K=sign_K, order_K=order_K,
        m2m_idx=m2m_idx, m2m_valid=m2m_valid,
        l2l_idx=l2l_idx, l2l_valid=l2l_valid,
        m2l_idx=m2l_idx, inv_fact_E=inv_fact_E,
        per_order_pos=per_order_pos,
    )


def _green(r):
    return 1.0 / jnp.sqrt(jnp.sum(r * r))


@lru_cache(maxsize=None)
def _deriv_fns(max_order: int):
    fns = [_green]
    f = _green
    for _ in range(max_order):
        f = jax.jacfwd(f)
        fns.append(f)
    return tuple(fns)


def _powers(d, m: int):
    """(3, m+1) table of d_i^j, j = 0..m, by repeated multiplication.

    A float `d ** j` lowers to a pow whose last bits depend on how XLA
    vectorizes the surrounding loop, so the same expression traced at two
    batch shapes (the per-tree and the batched upward pass) disagreed by a
    few ulps; products are correctly rounded wherever they run."""
    cols = [jnp.ones_like(d)]
    for _ in range(m):
        cols.append(cols[-1] * d)
    return jnp.stack(cols, axis=1)


def _pairwise_sum(a):
    """Sum over axis 0 as a fixed halving tree over the next power of two.

    `a.sum(axis=0)` leaves the association to XLA, which picks it per
    shape, so a leaf summed inside the batched (partitions x leaves) pass
    and the same leaf in the per-tree pass came out a few ulps apart.  With
    zero rows appended, any two widths whose nonzero rows share a prefix
    add in the same order."""
    n = a.shape[0]
    w = 1 << max(n - 1, 0).bit_length()
    if w != n:
        a = jnp.concatenate([a, jnp.zeros((w - n,) + a.shape[1:], a.dtype)])
    while a.shape[0] > 1:
        h = a.shape[0] // 2
        a = a[:h] + a[h:]
    return a[0]


class MultipoleOperators:
    """Order-p Cartesian Taylor operators; all methods map over leading dims."""

    def __init__(self, p: int = 4):
        self.p = p
        t = _tables(p)
        self.nk = t["nk"]
        self._K = jnp.asarray(t["K"])
        self._E = jnp.asarray(t["E"])
        self._inv_fact_K = jnp.asarray(t["inv_fact_K"])
        self._sign_K = jnp.asarray(t["sign_K"])
        self._m2m_idx = jnp.asarray(t["m2m_idx"])
        self._m2m_valid = jnp.asarray(t["m2m_valid"])
        self._l2l_idx = jnp.asarray(t["l2l_idx"])
        self._l2l_valid = jnp.asarray(t["l2l_valid"])
        self._m2l_idx = jnp.asarray(t["m2l_idx"])
        self._inv_fact_E = jnp.asarray(t["inv_fact_E"])
        self._per_order_pos = [jnp.asarray(x) for x in t["per_order_pos"]]
        self._max_order = 2 * (p - 1)

    # ---- building blocks -------------------------------------------------
    def _monomials_ext(self, d):
        """d^k for every extended multi-index k. d: (3,) -> (ne,)."""
        pows = _powers(d, self._max_order)                       # (3, max+1)
        return pows[0, self._E[:, 0]] * pows[1, self._E[:, 1]] * pows[2, self._E[:, 2]]

    def _monomials_k(self, d):
        K = self._K
        pows = _powers(d, self.p - 1)
        return pows[0, K[:, 0]] * pows[1, K[:, 1]] * pows[2, K[:, 2]]

    def derivs(self, d):
        """All derivative values D_k G(d) for |k| <= 2(p-1). d: (3,) -> (ne,)."""
        fns = _deriv_fns(self._max_order)
        parts = []
        for n in range(self._max_order + 1):
            full = fns[n](d)                      # tensor of shape (3,)*n
            flat = jnp.reshape(full, (-1,))
            parts.append(flat[self._per_order_pos[n]])
        return jnp.concatenate(parts)

    # ---- kernels ----------------------------------------------------------
    def p2m(self, q, x, center):
        """q: (n,), x: (n,3), center: (3,) -> (nk,). Padded bodies: q=0."""
        mono = jax.vmap(self._monomials_k)(x - center[None, :])   # (n, nk)
        return _pairwise_sum(q[:, None] * mono) * self._inv_fact_K

    def m2m(self, M, d):
        """Translate multipole by d = c_child - c_parent."""
        mono = self._monomials_ext(d)
        T = jnp.where(self._m2m_valid,
                      mono[self._m2m_idx] * self._inv_fact_E[self._m2m_idx], 0.0)
        return T @ M

    def m2l(self, M, d):
        """Multipole at c_M -> local at c_L; d = c_L - c_M."""
        D = self.derivs(d)                                       # (ne,)
        T = D[self._m2l_idx] * self._sign_K[None, :]             # (nk, nk)
        return T @ M

    def l2l(self, L, d):
        """Translate local by d = c_child - c_parent."""
        mono = self._monomials_ext(d)
        T = jnp.where(self._l2l_valid,
                      mono[self._l2l_idx] * self._inv_fact_E[self._l2l_idx], 0.0)
        return T @ L

    def l2p(self, L, y, center):
        """Evaluate local expansion at targets y: (n,3) -> (n,)."""
        mono = jax.vmap(self._monomials_k)(y - center[None, :])  # (n, nk)
        return mono @ (L * self._inv_fact_K)

    def m2p(self, M, y, center):
        """Direct multipole evaluation at targets (treecode-style; for tests)."""
        def one(yi):
            D = self.derivs(yi - center)
            return jnp.sum(M * self._sign_K * D[self._m2l_idx[0, :]])
        # m2l_idx[0, :] maps k -> index of (0 + k) = k in E
        return jax.vmap(one)(y)

    # ---- batched (vmapped) operators --------------------------------------
    # One vmap per operator, built once per operator set: the jitted executors
    # (fmm.py) and the batched multi-tree engine (repro.core.engine) map the
    # same closures over padded leaf/pair tables, so the traced subgraphs —
    # and therefore the JIT cache entries keyed on them — are shared.
    @cached_property
    def p2m_v(self):
        return jax.vmap(self.p2m)

    @cached_property
    def m2m_v(self):
        return jax.vmap(self.m2m)

    @cached_property
    def m2l_v(self):
        return jax.vmap(self.m2l)

    @cached_property
    def l2l_v(self):
        return jax.vmap(self.l2l)

    @cached_property
    def l2p_v(self):
        return jax.vmap(self.l2p)

    @cached_property
    def m2p_v(self):
        return jax.vmap(self.m2p)


# ---- P2P (reference; the Pallas kernel lives in repro.kernels.p2p) --------
def p2p(q_src, x_src, x_tgt, eps2=0.0):
    """Direct Laplace potential: phi_t = sum_s q_s / |x_t - x_s| (self term 0)."""
    d = x_tgt[:, None, :] - x_src[None, :, :]
    r2 = jnp.sum(d * d, axis=-1) + eps2
    inv_r = jnp.where(r2 > 0, jax.lax.rsqrt(jnp.maximum(r2, 1e-30)), 0.0)
    return inv_r @ q_src


@lru_cache(maxsize=None)
def get_operators(p: int = 4) -> "MultipoleOperators":
    """Cached operator set — reuse keeps jit caches warm across trees."""
    return MultipoleOperators(p)


# module-level convenience (order-4, the paper's configuration)
_OPS4 = None


def _ops4():
    global _OPS4
    if _OPS4 is None:
        _OPS4 = MultipoleOperators(4)
    return _OPS4


def p2m(q, x, center):
    return _ops4().p2m(q, x, center)


def m2m(M, d):
    return _ops4().m2m(M, d)


def m2l(M, d):
    return _ops4().m2l(M, d)


def l2l(L, d):
    return _ops4().l2l(L, d)


def l2p(L, y, center):
    return _ops4().l2p(L, y, center)


def m2p(M, y, center):
    return _ops4().m2p(M, y, center)
