"""TPU016 false-positive guard: the other accepted kernel-module shape
(``ops/pallas_knn.fused_impl``) — the platform guard lives in a
module-level ``*_impl`` rule that RETURNS the decision, and callers hand it
to the entry's ``interpret`` parameter (a program built once under
shard_map cannot call a wrapper per launch)."""
# tpulint: ops-module

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _scale_kernel(x_ref, o_ref):
    o_ref[:] = x_ref[:] * 2.0


def pallas_scale(x, *, interpret: bool):
    return pl.pallas_call(
        _scale_kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
        interpret=interpret,
    )(x)


def scale(x, *, impl: str, interpret: bool):
    if impl == "pallas":
        return pallas_scale(x, interpret=interpret)
    return x * jnp.float32(2.0)


def scale_impl(policy: str) -> tuple[str, bool]:
    platform = jax.devices()[0].platform
    if policy == "pallas" or (policy != "xla" and platform == "tpu"):
        return "pallas", platform == "cpu"
    return "xla", False
