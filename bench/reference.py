"""The plain reference the benchmark judges answers by, and its control.

`sums` gives the Laplace potential phi_i = sum_{j != i} q_j / |x_i - x_j|
as a float64 direct sum on the host, at a few targets, for one or more
charge vectors at once (the sum over sources is one matrix product, so
checking every call of a charge-update window costs little more than
checking one), and beside it each target's term scale
s_i = sqrt(sum_{j != i} q_j^2 / |x_i - x_j|^2), the root-sum-square of its
terms.  It imports nothing of the program under test.

The number compared is `scaled_rms`: the root mean square over the
targets of |phi_i - ref_i| / s_i.  Each target's error is measured against
its own terms, so no one target decides it; the relative L2 error over
the targets (`rel_l2`, reported beside it) is decided by the few targets
with the nearest neighbours, whose one large term swamps the rest.

`control_potential` is the same sum computed in bfloat16 on the device
after float32 differences, with float32 accumulation: the step below the
float32 the program states.  Put in the program's place, it has to come
out not correct (bench/control.py)."""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

SOURCE_BLOCK = 1 << 17        # sources per task: 8 x 2^17 x 8 B temporaries
TARGET_BLOCK = 8
THREADS = 6


def _block(xt, x, Q, lo, hi):
    xs = x[lo:hi]
    r2 = (xt[:, 0:1] - xs[:, 0]) ** 2
    r2 += (xt[:, 1:2] - xs[:, 1]) ** 2
    r2 += (xt[:, 2:3] - xs[:, 2]) ** 2
    inv = np.zeros_like(r2)
    np.divide(1.0, np.sqrt(r2), out=inv, where=r2 > 0)  # self term left out
    q = Q[lo:hi]
    return np.concatenate([inv @ q, (inv * inv) @ (q * q)], axis=1)


def potential(x: np.ndarray, Q: np.ndarray, targets: np.ndarray,
              threads: int = THREADS) -> np.ndarray:
    """Float64 potential at `x[targets]` of the charges `Q` ((N,) or
    (N, K)): shape (len(targets),) or (len(targets), K)."""
    return sums(x, Q, targets, threads)[0]


def sums(x: np.ndarray, Q: np.ndarray, targets: np.ndarray,
         threads: int = THREADS) -> tuple[np.ndarray, np.ndarray]:
    """(potential, term scale) at `x[targets]` of the charges `Q` ((N,) or
    (N, K)): each of shape (len(targets),) or (len(targets), K)."""
    x = np.asarray(x, np.float64)
    Q = np.asarray(Q, np.float64)
    one = Q.ndim == 1
    Q2 = Q[:, None] if one else Q
    xt = x[targets]
    blocks = [(lo, min(lo + SOURCE_BLOCK, len(x)))
              for lo in range(0, len(x), SOURCE_BLOCK)]

    def task(s):
        t = xt[s:s + TARGET_BLOCK]
        return sum(_block(t, x, Q2, lo, hi) for lo, hi in blocks)

    workers = max(1, min(threads, len(os.sched_getaffinity(0))))
    with ThreadPoolExecutor(max_workers=workers) as ex:
        out = np.concatenate(list(ex.map(task, range(0, len(xt),
                                                     TARGET_BLOCK))))
    k = Q2.shape[1]
    phi, scale = out[:, :k], np.sqrt(out[:, k:])
    return (phi[:, 0], scale[:, 0]) if one else (phi, scale)


def rel_l2(phi: np.ndarray, ref: np.ndarray) -> float:
    """Relative L2 error of `phi` against `ref` over the checked targets."""
    phi = np.asarray(phi, np.float64)
    return float(np.linalg.norm(phi - ref) / np.linalg.norm(ref))


def scaled_rms(phi: np.ndarray, ref: np.ndarray, scale: np.ndarray) -> float:
    """Root mean square over the checked targets of each one's error
    against its term scale: sqrt(mean(((phi - ref) / scale)^2))."""
    e = (np.asarray(phi, np.float64) - ref) / scale
    return float(np.sqrt(np.mean(e * e)))


def control_potential(x: np.ndarray, q: np.ndarray,
                      targets: np.ndarray) -> np.ndarray:
    """The reference computed in bfloat16 on the default device: positions
    and their differences in float32 (a bfloat16 position near |x| = 1 is
    ~4e-3 coarse, about the spacing of 10^6 bodies on the unit sphere, so
    near pairs would coincide), every operation after the difference in
    bfloat16 (charges, squares, their sum, the inverse root, the product),
    sums over sources in float32.  The self term is left out."""
    import jax
    import jax.numpy as jnp

    bf = jnp.bfloat16
    n = len(x)
    block = min(1 << 14, n)
    pad = (-n) % block
    far = np.full((pad, 3), 1e3)                  # padded sources: q = 0
    xs = jnp.asarray(np.concatenate([x, far]), jnp.float32)
    qs = jnp.asarray(np.pad(q, (0, pad)), bf)
    xt = jnp.asarray(x[targets], jnp.float32)

    @jax.jit
    def run(xt, xs, qs):
        def body(i, acc):
            xb = jax.lax.dynamic_slice_in_dim(xs, i * block, block)
            qb = jax.lax.dynamic_slice_in_dim(qs, i * block, block)
            d = (xt[:, None, :] - xb[None, :, :]).astype(bf)
            r2 = jnp.sum(d * d, axis=-1, dtype=bf)
            inv = jnp.where(r2 > 0, jax.lax.rsqrt(jnp.where(r2 > 0, r2, 1)),
                            jnp.zeros((), bf))
            return acc + jnp.sum((inv * qb[None, :]).astype(jnp.float32),
                                 axis=1)
        return jax.lax.fori_loop(0, xs.shape[0] // block, body,
                                 jnp.zeros(xt.shape[0], jnp.float32))

    return np.asarray(run(xt, xs, qs), np.float64)
