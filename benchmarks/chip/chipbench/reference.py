"""The plain reference: seeded stripe data and a CP-Azure encoder over GF(2^8).

Written from the code's definition and nothing of the program under test:

- the field is GF(2^8) with the primitive polynomial ``field_poly`` of the
  configuration (0x11D, the Jerasure/AES-style field);
- the r global parities are a Cauchy code over the data, with evaluation
  points ``a_i = r + i`` and ``b_j = j``: ``G_j = sum_i D_i / (b_j ^ a_i)``;
- CP-Azure splits the last global's coefficients over the p local groups,
  which are consecutive runs of data blocks, the shorter runs first:
  ``L_g = sum_{i in group g} beta_i D_i`` with ``beta = G_r``'s row, so that
  ``L_1 ^ ... ^ L_p == G_r``;
- block order within a stripe is data, then locals, then globals.

The data of a stripe is made on the device from ``(seed, stripe id)`` by the
same function in set-up and in the check, so the check regenerates it instead
of keeping it. Products run as shift-and-XOR over bytes packed four to a
32-bit word, which is plain integer arithmetic on any device.
"""
from __future__ import annotations

import functools

import numpy as np

SCHEMES = ("cp-azure",)


# ------------------------------------------------------------ field (numpy)
def field_tables(poly: int) -> tuple[np.ndarray, np.ndarray]:
    """``exp`` (510 entries) and ``log`` (256) tables of GF(2^8) mod ``poly``."""
    exp = np.zeros(510, np.int64)
    log = np.zeros(256, np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= poly
    exp[255:] = exp[:255]
    return exp, log


def field_mul(a: int, b: int, poly: int) -> int:
    """One product in GF(2^8), by shift and add."""
    out = 0
    while b:
        if b & 1:
            out ^= a
        b >>= 1
        a <<= 1
        if a & 0x100:
            a ^= poly
    return out


def field_inv(a: int, poly: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    exp, log = field_tables(poly)
    return int(exp[(255 - log[a]) % 255])


def generator(cfg: dict) -> np.ndarray:
    """The ``(n, k)`` generator of the configuration's code, rows in block
    order: identity for data, then locals, then globals."""
    if cfg["scheme"] not in SCHEMES:
        raise ValueError(f"the reference has no scheme {cfg['scheme']!r}")
    k, r, p, poly = cfg["k"], cfg["r"], cfg["p"], cfg["field_poly"]
    if k + r > 256:
        raise ValueError("k + r exceeds the field")
    alpha = np.array([[field_inv(j ^ (r + i), poly) for i in range(k)]
                      for j in range(r)], np.uint8)
    beta = alpha[r - 1]
    base, extra = divmod(k, p)
    sizes = [base] * (p - extra) + [base + 1] * extra
    locals_ = np.zeros((p, k), np.uint8)
    lo = 0
    for g, size in enumerate(sizes):
        locals_[g, lo:lo + size] = beta[lo:lo + size]
        lo += size
    return np.concatenate([np.eye(k, dtype=np.uint8), locals_, alpha])


# ------------------------------------------------------------ data (device)
def stripe_key(seed: int):
    import jax

    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


@functools.lru_cache(maxsize=None)
def _data_fn(k: int, block_size: int):
    import jax
    import jax.numpy as jnp

    def one(key, sid):
        words = jax.random.bits(jax.random.fold_in(key, sid),
                                (k, block_size // 4), jnp.uint32)
        return jax.lax.bitcast_convert_type(words, jnp.uint8).reshape(
            k, block_size)

    return jax.jit(jax.vmap(one, in_axes=(None, 0)))


def stripe_data(seed: int, sids, k: int, block_size: int):
    """``(S, k, block_size)`` uint8 data of stripes ``sids``, on the device."""
    import jax.numpy as jnp

    if block_size % 4:
        raise ValueError("block_size must be a multiple of 4")
    return _data_fn(k, block_size)(stripe_key(seed),
                                   jnp.asarray(sids, jnp.uint32))


# --------------------------------------------------------- encode (device)
@functools.lru_cache(maxsize=None)
def _parity_fn(rows: bytes, m: int, k: int, poly: int):
    import jax
    import jax.numpy as jnp

    coeffs = np.frombuffer(rows, np.uint8).reshape(m, k)
    low = np.uint32(poly & 0xFF)

    def xtime(x):
        carry = (x >> 7) & np.uint32(0x01010101)
        return ((x & np.uint32(0x7F7F7F7F)) << 1) ^ (carry * low)

    def parity(data):                      # (k, B) uint8 -> (m, B) uint8
        words = jax.lax.bitcast_convert_type(
            data.reshape(k, -1, 4), jnp.uint32)        # (k, B/4)
        planes = [words]
        for _ in range(7):
            planes.append(xtime(planes[-1]))
        planes = jnp.stack(planes)                     # (8, k, B/4): D * 2^t
        out = []
        for j in range(m):
            # bit t of coefficient (j, i) selects D_i * 2^t into the sum
            take = (coeffs[j][None, :] >> np.arange(8)[:, None]) & 1
            picked = jnp.where(jnp.asarray(take, bool)[:, :, None], planes,
                               np.uint32(0))
            out.append(jax.lax.reduce(picked, np.uint32(0),
                                      jax.lax.bitwise_xor, (0, 1)))
        out = jnp.stack(out)
        return jax.lax.bitcast_convert_type(out, jnp.uint8).reshape(m, -1)

    return jax.jit(parity)


def reference_stripe(seed: int, sid: int, cfg: dict) -> np.ndarray:
    """The whole ``(n, block_size)`` stripe ``sid`` as the code defines it,
    on the host."""
    k = cfg["k"]
    gen = generator(cfg)
    data = stripe_data(seed, [sid], k, cfg["block_size"])[0]
    fn = _parity_fn(gen[k:].tobytes(), gen.shape[0] - k, k,
                    cfg["field_poly"])
    return np.concatenate([np.asarray(data), np.asarray(fn(data))])
