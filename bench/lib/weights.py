"""Seeded random weights, made on the device in one jitted call, in the
type they are served in (bf16 matmul weights and biases, f32 norm gains).

The canonical dict is laid out by the architecture's reference
(bench/reference/<key>.py, `shapes(hf)`), which reads it as it is; the
architecture module (bench/arch/<key>.py, `program_params`) lays the same
arrays out as the program under test expects them, sharing every large
buffer. Values follow the published initialisation: matmul weights and the
embedding N(0, std), biases N(0, bias_std), norm gains 1 + N(0,
gain_jitter) (random rather than all ones, so that a norm that ignored its
gain would show in the check).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def jax_key(seed: int):
    """A PRNG key for any non-negative seed (also above 2**32)."""
    hi, lo = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray([hi, lo], jnp.uint32))


@partial(jax.jit, static_argnames=("spec", "std", "bias_std", "jitter"))
def _make(key, spec, std, bias_std, jitter):
    scale = {"w": std, "b": bias_std, "gain": jitter}
    out, i = {}, 0
    for group, leaves in spec:
        dst = out.setdefault(group, {}) if group else out
        for name, (shape, dtype, kind) in leaves:
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * scale[kind]
            dst[name] = (1.0 + x if kind == "gain" else x).astype(dtype)
            i += 1
    return out


def _frozen(sh: dict):
    """The leaves in drawing order: each group's (a dict value) sorted by
    name, the groups by name, then the top-level leaves sorted by name.
    Leaf i is drawn from fold_in(key, i), so the order fixes the values."""
    groups = tuple((g, tuple(sorted(sh[g].items())))
                   for g in sorted(k for k, v in sh.items()
                                   if isinstance(v, dict)))
    top = tuple(sorted((k, v) for k, v in sh.items()
                       if not isinstance(v, dict)))
    return groups + ((None, top),)


def make(shapes: dict, init: dict, seed: int) -> dict:
    """The canonical weights for one seed, on the default device, for the
    leaves a reference's shapes(hf) names."""
    return _make(jax_key(seed), _frozen(shapes), float(init["std"]),
                 float(init["bias_std"]), float(init["gain_jitter"]))
