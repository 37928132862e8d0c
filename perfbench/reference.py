"""Plain reference for the benchmark's correctness check.

Imports nothing of the program under test and nothing of JAX, so it can
run in worker processes while the chip is held by the parent:

* the LASSO instance generator (the paper's §V-A Gaussian design);
* textbook Paillier (keygen from a seed, CRT decryption after Paillier
  1999 §7) to read the program's ciphertexts back;
* checks of each homomorphic operation's answer against its inputs;
* float64 distributed (Jacobi) ADMM for LASSO, the iteration that the
  private protocol wraps (paper eq. 10).
"""
from __future__ import annotations

import math
import random

import numpy as np

# ---------------------------------------------------------------------------
# instance generator
# ---------------------------------------------------------------------------


def make_lasso(M: int, N: int, sparsity: float, noise: float, seed):
    """(A, y, x_true): Gaussian A scaled by 1/sqrt(M), a ``sparsity``
    share of nonzero coefficients, and Gaussian noise on y = A x."""
    rng = np.random.default_rng(seed)
    A = rng.normal(0.0, 1.0, (M, N)) / np.sqrt(M)
    k = max(1, int(round(sparsity * N)))
    x = np.zeros(N)
    idx = rng.choice(N, k, replace=False)
    x[idx] = rng.normal(0.0, 1.0, k)
    y = A @ x + noise * rng.normal(0.0, 1.0, M)
    return A, y, x


# ---------------------------------------------------------------------------
# Paillier
# ---------------------------------------------------------------------------

_SMALL_PRIMES = (2, 3, 5, 7, 11, 13, 17, 19, 23, 29, 31, 37, 41, 43, 47, 53,
                 59, 61, 67, 71, 73, 79, 83, 89, 97)


def _probable_prime(n: int, rng: random.Random, rounds: int = 40) -> bool:
    if n < 2:
        return False
    for p in _SMALL_PRIMES:
        if n % p == 0:
            return n == p
    d, s = n - 1, 0
    while d % 2 == 0:
        d //= 2
        s += 1
    for _ in range(rounds):
        x = pow(rng.randrange(2, n - 1), d, n)
        if x in (1, n - 1):
            continue
        for _ in range(s - 1):
            x = x * x % n
            if x == n - 1:
                break
        else:
            return False
    return True


def _prime(bits: int, rng: random.Random) -> int:
    while True:
        cand = rng.getrandbits(bits) | (1 << (bits - 1)) | 1
        if _probable_prime(cand, rng):
            return cand


def keygen(bits: int, seed: int) -> tuple[int, int]:
    """The primes (p, q) that the deployment's key seed fixes: Miller-Rabin
    candidates drawn from ``random.Random(seed)``, p of bits//2 bits, q of
    the rest, redrawn while p == q or gcd(pq, (p-1)(q-1)) != 1."""
    rng = random.Random(seed)
    while True:
        p = _prime(bits // 2, rng)
        q = _prime(bits - bits // 2, rng)
        if p != q and math.gcd(p * q, (p - 1) * (q - 1)) == 1:
            return p, q


class Key:
    """Private key (p, q) with g = n + 1, and CRT decryption."""

    def __init__(self, p: int, q: int):
        self.p, self.q = p, q
        self.n = p * q
        self.n2 = self.n * self.n
        self.lam = math.lcm(p - 1, q - 1)
        self.p2, self.q2 = p * p, q * q
        g = self.n + 1
        self.hp = pow(self._lp(pow(g, p - 1, self.p2)), -1, p)
        self.hq = pow(self._lq(pow(g, q - 1, self.q2)), -1, q)
        self.p_inv_q = pow(p, -1, q)

    def _lp(self, x: int) -> int:
        return (x - 1) // self.p

    def _lq(self, x: int) -> int:
        return (x - 1) // self.q

    def decrypt(self, c: int) -> int:
        """m in [0, n) with c = (1 + n)^m r^n mod n^2."""
        mp = self._lp(pow(c % self.p2, self.p - 1, self.p2)) * self.hp % self.p
        mq = self._lq(pow(c % self.q2, self.q - 1, self.q2)) * self.hq % self.q
        return mp + (mq - mp) * self.p_inv_q % self.q * self.p


def decrypt_many(pq: tuple[int, int], cs: list[int]) -> list[int]:
    """Decrypt a list of ciphertexts (one worker's share)."""
    key = Key(*pq)
    return [key.decrypt(c) for c in cs]


# ---------------------------------------------------------------------------
# answers of the homomorphic operations, judged by their inputs
# ---------------------------------------------------------------------------


def valid_ciphertext(key: Key, c: int) -> bool:
    """In Z*_{n^2} and blinded: r^n = 1 mod n would mean no blinding."""
    return 0 < c < key.n2 and c % key.n != 1 and math.gcd(c, key.n) == 1


def op_errors(key: Key, op: str, args: tuple, out: list, dec) -> int:
    """Number of wrong elements in one operation's answer.

    ``dec`` maps a ciphertext to its plaintext (memoised by the caller).
    enc: args (m,), out ciphertexts of m mod n.  add: args (a, b), out
    ciphertexts of dec(a) + dec(b).  matvec: args (K, s), out[i]
    ciphertext of sum_j K[i, j] dec(s[j]).  dec: args (c,), out plaintexts.
    """
    n = key.n
    bad = 0
    if op == "enc":
        for m, c in zip(args[0], out):
            bad += not (valid_ciphertext(key, c) and dec(c) == m % n)
    elif op == "add":
        for a, b, c in zip(args[0], args[1], out):
            bad += not (valid_ciphertext(key, c)
                        and dec(c) == (dec(a) + dec(b)) % n)
    elif op == "matvec":
        Km, s = args
        ms = [dec(c) for c in s]
        for row, c in zip(Km, out):
            want = sum(int(k) * m for k, m in zip(row, ms)) % n
            bad += not (valid_ciphertext(key, c) and dec(c) == want)
    elif op == "dec":
        for c, m in zip(args[0], out):
            bad += int(m) != dec(c)
    else:
        raise ValueError(f"unknown operation {op!r}")
    return bad + abs(len(out) - len(args[0]))   # missing or extra answers


# ---------------------------------------------------------------------------
# float ADMM
# ---------------------------------------------------------------------------


def soft_threshold(x: np.ndarray, t: float) -> np.ndarray:
    return np.sign(x) * np.maximum(np.abs(x) - t, 0.0)


def distributed_admm(A: np.ndarray, y: np.ndarray, K: int, rho: float,
                     lam: float, rounds: int) -> np.ndarray:
    """Synchronous distributed ADMM for LASSO over K column blocks, with
    y/K per block (paper eq. 10):  x_k <- B_k (A_k^T y/K + rho (z_k - v_k)),
    z <- S_{lam/rho}(v + x_prev), v <- v + x_prev - z, all from the
    previous round's iterate.  Returns the iterate after ``rounds``."""
    M, N = A.shape
    Nk = N // K
    blocks = [slice(k * Nk, (k + 1) * Nk) for k in range(K)]
    Bs, alphas = [], []
    for sl in blocks:
        Ak = A[:, sl]
        B = np.linalg.inv(Ak.T @ Ak + rho * np.eye(Nk))
        Bs.append(B)
        alphas.append(B @ (Ak.T @ (y / K)))
    x = np.zeros(N)
    z = np.zeros(N)
    v = np.zeros(N)
    for _ in range(rounds):
        x_new = np.concatenate([a + rho * B @ (z[sl] - v[sl])
                                for a, B, sl in zip(alphas, Bs, blocks)])
        z = soft_threshold(v + x, lam / rho)
        v = v + x - z
        x = x_new
    return x
