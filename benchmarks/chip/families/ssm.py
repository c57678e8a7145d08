"""The ssm family: Mamba-2 blocks (state-space duality, arXiv:2405.21060),
served by the program's ``ssm`` architecture.  No whole-prompt prefill:
the program steps a prompt one position per call."""

import math

import jax
import jax.numpy as jnp

from chipbench.work import itemsize, leaf_bytes

F32 = jnp.float32

ARCH_TYPE = "ssm"
PROGRAM_KEYS = {"state_size": "ssm_state", "head_dim": "ssm_head_dim",
                "expand": "ssm_expand", "conv_kernel": "conv_kernel",
                "n_groups": "ssm_groups"}


def _widths(m):
    """Inner width, heads, B/C width (groups x state), conv channels."""
    di = m["expand"] * m["hidden_size"]
    gn = m["n_groups"] * m["state_size"]
    return di, di // m["head_dim"], gn, di + 2 * gn


def leaves(m):
    d, l = m["hidden_size"], m["num_hidden_layers"]
    wd = jnp.dtype(m["dtype"])
    di, h, gn, c = _widths(m)
    k = m["conv_kernel"]
    return {
        "layers/ln": ((l, d), wd, "norm"),
        "layers/w_in": ((l, d, 2 * di + 2 * gn + h), wd, "normal"),
        "layers/conv_w": ((l, k, c), wd, "conv"),
        "layers/conv_b": ((l, c), wd, "conv"),
        "layers/dt_bias": ((l, h), F32, "dt_bias"),
        "layers/a_log": ((l, h), F32, "a_log"),
        "layers/d_skip": ((l, h), F32, "norm"),
        "layers/gate_norm": ((l, di), wd, "norm"),
        "layers/w_out": ((l, di, d), wd, "out"),
    }


def init(kind, key, shape, dtype):
    if kind == "conv":
        return jax.random.uniform(key, shape, F32, -0.5, 0.5).astype(dtype)
    if kind == "a_log":  # A = -exp(a_log) in -[1, 16]
        return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))
    if kind == "dt_bias":  # softplus(dt_bias) = dt, log-uniform [1e-3, 1e-1]
        dt = jnp.exp(jax.random.uniform(
            key, shape, F32, math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(kind)


def decode_stack(m, context):
    """The whole recurrent state read and written, whatever the context."""
    w = itemsize(m)
    d, l = m["hidden_size"], m["num_hidden_layers"]
    di, h, gn, c = _widths(m)
    n = m["state_size"]
    flops = (2 * d * (2 * di + 2 * gn + h) + 2 * di * d  # projections
             + 2 * m["conv_kernel"] * c  # conv
             + 6 * di * n)  # state decay, update and read-out
    ssm = di * n * 4  # float32 state
    conv = (m["conv_kernel"] - 1) * c * w
    return l * flops, leaf_bytes(leaves(m)) + l * 2 * (ssm + conv)
