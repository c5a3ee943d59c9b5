"""The float64 reference that decides ``correct``, and the control.

An answer x to A x = b is judged by its true relative residual
||b - A x|| / ||b||, with A the configuration's float64 reference operator
(``chipbench/operators/<kind>.py``: scipy CSR for stored operators, a
numpy stencil for matrix-free ones) and b the float32 right-hand side the
program was given, widened exactly.  Nothing here imports the program.

The control is the reference solver put in the program's place one
precision below the configuration's: Jacobi-PCG with the program's
stopping rule (recursive ||r|| <= rtol ||b||), computed in bfloat16 for a
float32 configuration.  Its answers must fail the limit.
"""

from __future__ import annotations

import numpy as np

# the precision the control computes in, one below the configuration's
CONTROL_DTYPE = {"float32": "bfloat16", "float64": "float32"}


def rel_residual(matvec, b: np.ndarray, x: np.ndarray) -> float:
    b = np.asarray(b, np.float64)
    r = b - matvec(np.asarray(x, np.float64))
    return float(np.linalg.norm(r) / np.linalg.norm(b))


def control_solver(cfg: dict, operator, max_iters: int):
    """A jitted ``b -> (x, iterations, final recursive ||r||)``: Jacobi-PCG
    in the control's precision on the device, stopping at the
    configuration's rtol or after ``max_iters`` iterations."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(CONTROL_DTYPE[cfg["solver"]["dtype"]])
    matvec = operator.control_matvec(cfg, dtype)
    dinv = jnp.asarray(1.0 / operator.diagonal(cfg), dtype)
    rtol = float(cfg["solver"]["rtol"])

    def dot(u, v):
        return jnp.sum(u * v, dtype=dtype)

    @jax.jit
    def solve(b):
        b = b.astype(dtype)
        bn = jnp.sqrt(dot(b, b))
        z = dinv * b
        state = (jnp.zeros_like(b), b, z, dot(b, z), jnp.int32(0))

        def cond(s):
            _, r, _, _, k = s
            return (jnp.sqrt(dot(r, r)) > rtol * bn) & (k < max_iters)

        def body(s):
            x, r, p, rz, k = s
            ap = matvec(p)
            alpha = rz / dot(p, ap)
            x = x + alpha * p
            r = r - alpha * ap
            z = dinv * r
            rz_new = dot(r, z)
            return x, r, z + (rz_new / rz) * p, rz_new, k + 1

        x, r, _, _, k = jax.lax.while_loop(cond, body, state)
        return x, k, jnp.sqrt(dot(r, r))

    return solve
