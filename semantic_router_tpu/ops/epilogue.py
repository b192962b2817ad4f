"""Pallas fused dense+bias+activation epilogue for the head bank.

The all-heads head-bank matmul (models.lora.apply_head_bank) is the one
hot-path matmul the trunk-collapse PRs left un-tuned: XLA lowers it as
``einsum → add(bias) → add(lora delta) → gelu`` — up to three extra
element-wise dispatches touching a [B, T, H] intermediate per step.
This kernel streams the same math through the MXU once per (task,
row-block) tile with the bias add, optional LoRA delta add, and the
activation applied in-register before the tile ever leaves VMEM
(SURVEY hard-part 1: the step budget lives or dies on dispatch count).

Layout: x [rows, D] (pooled rows, or [B·S, D] for token heads);
kernel [T, D, H]; grid = (T, rows/BLOCK_ROWS).  Inside the kernel the
task axis LEADS (bias [T, 1, H], delta and output [T, rows, H]) so every
block's last two dims are (rows-block, H) or (1, H) — the TPU tiling
rule refuses a (rows-block, 1, H) block of a [rows, T, H] array; the
wrapper transposes at the boundary.  The LoRA delta — two skinny rank-r
matmuls — stays an XLA einsum OUTSIDE the kernel (skinny lanes tile
poorly on the MXU) and enters as a precomputed [rows, T, H] operand
added before the activation, so LoRA'd and plain banks share one kernel.

``head_epilogue`` is the public entry: the Pallas kernel on TPU, the
pure-XLA reference on CPU — same semantics; the reference doubles as
the numerics oracle in tests via interpret mode (docs/KERNELS.md
"interpret-mode caveat": CPU tier-1 drives the kernel interpreted for
parity, never for speed).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_ROWS = 256


def gelu_exact(x: jnp.ndarray) -> jnp.ndarray:
    """erf-form GELU — the classifier activation every ModernBERT head
    uses (models.modernbert.activation("gelu") returns THIS function, so
    the kernel below can recognise it)."""
    return jax.nn.gelu(x, approximate=False)


def _gelu_exact_in_kernel(x: jnp.ndarray) -> jnp.ndarray:
    """gelu_exact from primitives Mosaic lowers: the TPU Pallas lowering
    of the installed JAX has no rule for erf/erfc, so erf is
    Abramowitz–Stegun 7.1.26 (|error| ≤ 1.5e-7, i.e. ≤ 1e-7·|x| on the
    GELU — inside the 1e-4 parity gate of tests/test_kernels.py)."""
    z = jnp.abs(x) * 0.7071067811865476
    t = 1.0 / (1.0 + 0.3275911 * z)
    poly = t * (0.254829592 + t * (-0.284496736 + t * (
        1.421413741 + t * (-1.453152027 + t * 1.061405429))))
    erf = jnp.sign(x) * (1.0 - poly * jnp.exp(-z * z))
    return 0.5 * x * (1.0 + erf)


def _epilogue_kernel(x_ref, w_ref, b_ref, d_ref, o_ref, *,
                     act: Callable):
    """One (task, row-block) program: matmul + bias + delta + act."""
    x = x_ref[...].astype(jnp.float32)            # [Br, D]
    w = w_ref[0].astype(jnp.float32)              # [D, H]
    h = jnp.dot(x, w, preferred_element_type=jnp.float32)
    if b_ref is not None:
        h = h + b_ref[0].astype(jnp.float32)      # [1, H]
    if d_ref is not None:
        h = h + d_ref[0].astype(jnp.float32)      # [Br, H]
    o_ref[0] = act(h).astype(o_ref.dtype)


def head_epilogue_pallas(x: jnp.ndarray, kernel: jnp.ndarray,
                         bias: Optional[jnp.ndarray],
                         delta: Optional[jnp.ndarray],
                         act: Callable,
                         block_rows: int = DEFAULT_BLOCK_ROWS,
                         interpret: Optional[bool] = None) -> jnp.ndarray:
    """x [rows, D] × kernel [T, D, H] (+ bias [T, H]) (+ delta
    [rows, T, H]) → act(x@W + b + delta) [rows, T, H].

    ``interpret``: None = the Pallas interpreter on a CPU platform (so
    the same call site runs in tests), the compiled kernel everywhere
    else.  Compiled, an ``act`` that traces to a primitive Mosaic cannot
    lower raises the compiler's error — it is never served by the
    reference under this kernel's name."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if not interpret and act is gelu_exact:
        act = _gelu_exact_in_kernel
    rows, D = x.shape
    T, _, H = kernel.shape
    br = min(block_rows, max(rows, 1))
    pad = (-rows) % br
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        if delta is not None:
            delta = jnp.pad(delta, ((0, pad), (0, 0), (0, 0)))
    rp = rows + pad

    in_specs = [
        pl.BlockSpec((br, D), lambda t, r: (r, 0)),
        pl.BlockSpec((1, D, H), lambda t, r: (t, 0, 0)),
    ]
    operands = [x, kernel]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, 1, H), lambda t, r: (t, 0, 0)))
        operands.append(bias[:, None, :])
    if delta is not None:
        in_specs.append(pl.BlockSpec((1, br, H), lambda t, r: (t, r, 0)))
        operands.append(jnp.swapaxes(delta, 0, 1))

    def kern(*refs):
        x_ref, w_ref = refs[0], refs[1]
        i = 2
        b_ref = d_ref = None
        if bias is not None:
            b_ref = refs[i]
            i += 1
        if delta is not None:
            d_ref = refs[i]
            i += 1
        _epilogue_kernel(x_ref, w_ref, b_ref, d_ref, refs[-1], act=act)

    out = pl.pallas_call(
        kern,
        grid=(T, rp // br),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, br, H), lambda t, r: (t, r, 0)),
        out_shape=jax.ShapeDtypeStruct((T, rp, H), x.dtype),
        interpret=interpret,
    )(*operands)
    return jnp.swapaxes(out, 0, 1)[:rows]


def head_epilogue_reference(x: jnp.ndarray, kernel: jnp.ndarray,
                            bias: Optional[jnp.ndarray],
                            delta: Optional[jnp.ndarray],
                            act: Callable) -> jnp.ndarray:
    """The pure-XLA epilogue — exactly the pre-kernel einsum math, kept
    as the CPU serving path and the parity oracle."""
    h = jnp.einsum("bd,tdh->bth", x, kernel)
    if bias is not None:
        h = h + bias[None]
    if delta is not None:
        h = h + delta
    return act(h)


def head_epilogue(x: jnp.ndarray, kernel: jnp.ndarray,
                  bias: Optional[jnp.ndarray] = None,
                  delta: Optional[jnp.ndarray] = None,
                  act: Callable = lambda h: h) -> jnp.ndarray:
    """Dispatch: the Pallas kernel on a TPU platform; the XLA reference
    on CPU."""
    if jax.default_backend() == "tpu":
        return head_epilogue_pallas(x, kernel, bias, delta, act)
    return head_epilogue_reference(x, kernel, bias, delta, act)
