"""Plain TeZO-Adam (Algorithm 1 of arXiv:2501.19057 with the separable
second moment of its Eq. 8), for the first steps of a run.

The state is held as the method defines it: bfloat16 weights, rounded
after every pass; frozen CPD factors ``u [.., m, r]``, ``v [.., n, r]``
drawn once; a fresh temporal factor ``tau [.., r]`` per step and leaf;
r-vector moments ``tau_m``, ``tau_v``.  A step is

    W <- bf16(W + rho Z)        f+ = loss(W)
    W <- bf16(W - 2 rho Z)      f- = loss(W)
    kappa = (f+ - f-) / (2 rho)
    W <- bf16(W + rho Z)        (restore)
    tau_m <- b1 tau_m + (1 - b1) kappa tau
    tau_v <- b2 tau_v + (1 - b2) kappa^2 tau^2
    W <- bf16(W - lr M / sqrt(V + eps)),
         M = (u diag tau_m) v^T,  V = (u^2 diag tau_v) (v^2)^T

with Z = (u diag tau) v^T.  Leaves whose last two dims are not both at
least 8 (the final norm gain) take dense MeZO noise z ~ N(0, I) instead,
rounded to the weight dtype, with dense Adam moments.

Random draws follow the keyed scheme the method specifies: factors from
``fold_in(fold_in(fold_in(K, 0xF0), 1), h(path + "#u"|"#v"))``, the step
key ``fold_in(fold_in(K, 0x5EED), step)``, tau from
``fold_in(fold_in(key_t, probe), h(path + "#tau"))`` and dense noise from
``h(path + "#dense")``, where K = PRNGKey(seed) and h is the first four
bytes (little-endian) of the path's SHA-256, masked to 31 bits.  Paths are
the leaf paths of the parameter tree, e.g. ``['blocks']['wq']``.

Stacked layer leaves are worked one layer at a time in float32, so the
reference fits beside its own bfloat16 weights.
"""
from __future__ import annotations

import functools
import hashlib

import jax
import jax.numpy as jnp
from jax import lax

from bench import weights as W
from bench.reference import transformer as T

HIGHEST = lax.Precision.HIGHEST


def path_hash(path: str) -> int:
    return int.from_bytes(hashlib.sha256(path.encode()).digest()[:4],
                          "little") & 0x7FFFFFFF


def key_for(key, path: str):
    return jax.random.fold_in(key, path_hash(path))


def block_path(name: str) -> str:
    return f"['blocks']['{name}']"


def is_lowrank(shape) -> bool:
    return len(shape) >= 2 and shape[-2] >= 8 and shape[-1] >= 8


# ---------------------------------------------------------------------------
# per-slice arithmetic
# ---------------------------------------------------------------------------


@jax.jit
def _recon(u, v, t):
    return jnp.einsum("...mr,...nr->...mn", u * t[..., None, :], v,
                      precision=HIGHEST)


@jax.jit
def _add(w, u, v, tau, scale):
    """bf16(w + scale (u diag tau) v^T)."""
    z = _recon(u, v, tau)
    return (w.astype(jnp.float32) + scale * z).astype(w.dtype)


@functools.partial(jax.jit, static_argnames=("eps",))
def _adam(w, u, v, tau_m, tau_v, lr, *, eps):
    m = _recon(u, v, tau_m)
    vv = _recon(u * u, v * v, tau_v)
    return (w.astype(jnp.float32) - lr * m * lax.rsqrt(vv + eps)).astype(
        w.dtype)


@jax.jit
def _dense_add(w, z, scale):
    return (w.astype(jnp.float32) + scale * z.astype(jnp.float32)).astype(
        w.dtype)


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_adam(w, m, v, lr, *, eps):
    return (w.astype(jnp.float32) - lr * m * lax.rsqrt(v + eps)).astype(
        w.dtype)


@jax.jit
def _sq_diff(a, b):
    d = a.astype(jnp.float32) - b.astype(jnp.float32)
    return jnp.sum(d * d)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


class Reference:
    """Reference state for one seed: weights regenerated from the
    benchmark's weight key, factors and noise from the method's seed."""

    def __init__(self, m: dict, zo: dict, weights_key, zo_seed: int):
        self.m, self.zo = m, zo
        self.wkey = weights_key
        L = m["n_layers"]
        gen_layer = jax.jit(lambda k, l: W.layer_params(m, k, l))
        self.layers = [gen_layer(weights_key, l) for l in range(L)]
        self.outer = jax.jit(lambda k: W.outer_params(m, k))(weights_key)
        # norm gains are leaves of their own, [L, D], in the program tree
        self.norms = {
            n: jnp.stack([self.layers[l].pop(n) for l in range(L)])
            for n in ("ln1", "ln2")
        }
        root = jax.random.PRNGKey(zo_seed)
        self.base_key = jax.random.fold_in(root, 0x5EED)
        fkey = jax.random.fold_in(jax.random.fold_in(root, 0xF0), 1)
        self.factors = {}
        self.moments = {}
        for path, shape in self.leaf_shapes().items():
            if is_lowrank(shape):
                m_, n_ = shape[-2], shape[-1]
                r = max(1, min(zo["rank"], m_, n_))
                batch = shape[:-2]
                u = jax.random.normal(key_for(fkey, path + "#u"),
                                      batch + (m_, r), jnp.float32)
                v = jax.random.normal(key_for(fkey, path + "#v"),
                                      batch + (n_, r), jnp.float32)
                self.factors[path] = (u, v)
                self.moments[path] = (jnp.zeros(batch + (r,)),
                                      jnp.zeros(batch + (r,)))
            else:
                self.moments[path] = (jnp.zeros(shape), jnp.zeros(shape))

    def leaf_shapes(self) -> dict:
        L = self.m["n_layers"]
        out = {p: tuple(a.shape) for p, a in
               ((f"['{k}']", v) for k, v in self.outer.items())}
        for n, a in self.norms.items():
            out[block_path(n)] = tuple(a.shape)
        for n, a in self.layers[0].items():
            out[block_path(n)] = (L,) + tuple(a.shape)
        return out

    # ---- leaf access ------------------------------------------------------
    def _get(self, path):
        for k in self.outer:
            if path == f"['{k}']":
                return self.outer[k]
        for n in self.norms:
            if path == block_path(n):
                return self.norms[n]
        return None  # a stacked matrix leaf, held per layer

    def _set(self, path, value):
        for k in self.outer:
            if path == f"['{k}']":
                self.outer[k] = value
                return
        for n in self.norms:
            if path == block_path(n):
                self.norms[n] = value
                return
        raise KeyError(path)

    def _layer_name(self, path):
        return path[len("['blocks']['"):-2]

    # ---- random draws -----------------------------------------------------
    def tau(self, key_t, path, probe=0):
        u, _ = self.factors[path]
        return jax.random.normal(
            key_for(jax.random.fold_in(key_t, probe), path + "#tau"),
            u.shape[:-2] + (u.shape[-1],), jnp.float32)

    def noise(self, key_t, path, shape, probe=0):
        z = jax.random.normal(
            key_for(jax.random.fold_in(key_t, probe), path + "#dense"),
            shape, jnp.float32)
        return z.astype(jnp.dtype(self.m["dtype"]))

    # ---- passes -----------------------------------------------------------
    def perturb(self, key_t, scale):
        for path in self.leaf_shapes():
            if path in self.factors:
                u, v = self.factors[path]
                tau = self.tau(key_t, path)
                w = self._get(path)
                if w is not None:
                    self._set(path, _add(w, u, v, tau, scale))
                else:
                    n = self._layer_name(path)
                    for l, lp in enumerate(self.layers):
                        lp[n] = _add(lp[n], u[l], v[l], tau[l], scale)
            else:
                w = self._get(path)
                self._set(path, _dense_add(
                    w, self.noise(key_t, path, w.shape), scale))

    def update(self, key_t, kappa):
        zo = self.zo
        b1, b2, lr, eps = zo["beta1"], zo["beta2"], zo["lr"], zo["eps"]
        rho = zo["rho"]
        self.perturb(key_t, rho)  # the restore of the last probe
        for path in self.leaf_shapes():
            tm, tv = self.moments[path]
            if path in self.factors:
                u, v = self.factors[path]
                tau = self.tau(key_t, path)
                tm = b1 * tm + (1 - b1) * (kappa * tau)
                tv = b2 * tv + (1 - b2) * ((kappa * kappa) * (tau * tau))
                self.moments[path] = (tm, tv)
                w = self._get(path)
                if w is not None:
                    self._set(path, _adam(w, u, v, tm, tv, lr, eps=eps))
                else:
                    n = self._layer_name(path)
                    for l, lp in enumerate(self.layers):
                        lp[n] = _adam(lp[n], u[l], v[l], tm[l], tv[l], lr,
                                      eps=eps)
            else:
                w = self._get(path)
                g = kappa * self.noise(key_t, path, w.shape).astype(
                    jnp.float32)
                tm = b1 * tm + (1 - b1) * g
                tv = b2 * tv + (1 - b2) * g * g
                self.moments[path] = (tm, tv)
                self._set(path, _dense_adam(w, tm, tv, lr, eps=eps))

    def loss(self, batch, precision):
        return T.loss(self.m, self.layers, self.norms["ln1"],
                      self.norms["ln2"], self.outer, batch["tokens"],
                      batch["targets"], precision)

    def step(self, step: int, batch, precision="f32") -> dict:
        rho = self.zo["rho"]
        key_t = jax.random.fold_in(self.base_key, step)
        self.perturb(key_t, +rho)
        f_plus = self.loss(batch, precision)
        self.perturb(key_t, -2.0 * rho)
        f_minus = self.loss(batch, precision)
        kappa = (f_plus - f_minus) / (2.0 * rho)
        self.update(key_t, kappa)
        return {"loss": float((f_plus + f_minus) / 2.0),
                "kappa": float(kappa)}

    # ---- readings ---------------------------------------------------------
    def grad_norms(self) -> dict:
        """Per leaf, the norm of the gradient estimate the optimizer took
        in: its first moment after one step over (1 - beta1)."""
        b1 = self.zo["beta1"]
        return {p: float(jnp.linalg.norm(tm.ravel())) / (1 - b1)
                for p, (tm, _) in self.moments.items()}

    def change_norms(self) -> dict:
        """Per leaf, ||W - W0|| with W0 regenerated from the weight key."""
        m = self.m
        gen_layer = jax.jit(lambda k, l: W.layer_params(m, k, l))
        outer0 = jax.jit(lambda k: W.outer_params(m, k))(self.wkey)
        out = {f"['{k}']": float(jnp.sqrt(_sq_diff(self.outer[k], outer0[k])))
               for k in self.outer}
        sq = {}
        for l, lp in enumerate(self.layers):
            p0 = gen_layer(self.wkey, l)
            for n in ("ln1", "ln2"):
                sq[n] = sq.get(n, 0.0) + float(
                    _sq_diff(self.norms[n][l], p0[n]))
            for n, a in lp.items():
                sq[n] = sq.get(n, 0.0) + float(_sq_diff(a, p0[n]))
        out.update({block_path(n): s ** 0.5 for n, s in sq.items()})
        return out


def run(m: dict, zo: dict, weights_key, zo_seed: int, batches,
        precision: str = "f32") -> dict:
    """Steps over ``batches`` from a fresh state; the readings that the
    comparison takes: each step's loss, the first step's gradient norms and
    the parameter change after the last step."""
    ref = Reference(m, zo, weights_key, zo_seed)
    losses, grads = [], None
    for s, batch in enumerate(batches):
        losses.append(ref.step(s, batch, precision)["loss"])
        if s == 0:
            grads = ref.grad_norms()
    return {"losses": losses, "grad_norms": grads,
            "change_norms": ref.change_norms()}
