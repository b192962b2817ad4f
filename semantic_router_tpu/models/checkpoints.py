"""Reading a checkpoint directory into a decoder's parameter tree: one
tensor (or a slice of its rows) at a time, converted to the model's dtype
on the default device.
"""

from __future__ import annotations

import contextlib
import json
import os
from typing import Any, Dict, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np


def torch_dtype_of(name) -> Any:
    """A checkpoint's ``torch_dtype`` (``"bfloat16"``, ``torch.bfloat16``)
    as the dtype a model computes in; anything unknown is float32."""
    return {"bfloat16": jnp.bfloat16, "float16": jnp.float16}.get(
        str(name).replace("torch.", ""), jnp.float32)


@contextlib.contextmanager
def checkpoint_reader(path: str):
    """``get(name) -> tensor`` over a checkpoint directory
    (``model.safetensors``, or sharded files with
    ``model.safetensors.index.json``), one tensor loaded per call;
    ``get.rows(name, first, count)`` reads those rows of it alone."""
    from safetensors import safe_open

    index = os.path.join(path, "model.safetensors.index.json")
    if os.path.exists(index):
        with open(index) as f:
            where = json.load(f)["weight_map"]
    else:
        with safe_open(os.path.join(path, "model.safetensors"), "np") as f:
            where = {k: "model.safetensors" for k in f.keys()}
    handles: Dict[str, Any] = {}

    def handle(name: str):
        fname = where[name]
        if fname not in handles:
            handles[fname] = safe_open(os.path.join(path, fname), "np")
        return handles[fname]

    def get(name: str) -> np.ndarray:
        return handle(name).get_tensor(name)

    get.rows = lambda name, first, count: \
        handle(name).get_slice(name)[first:first + count]

    try:
        yield get
    finally:
        handles.clear()


def tensor_rows(get, name: str, first: int, count: int) -> np.ndarray:
    """Rows ``[first, first + count)`` of tensor ``name``: through
    ``get.rows`` where the reader can slice a file (only those rows are
    read), else off the whole tensor."""
    if hasattr(get, "rows"):
        return get.rows(name, first, count)
    return np.asarray(get(name))[first:first + count]


def on_device(cfg, a: np.ndarray, transpose: bool = False) -> jnp.ndarray:
    """``a`` in ``cfg.dtype`` on the default device, its last two axes
    swapped if ``transpose`` (a published ``[out, in]`` matrix as the
    ``[in, out]`` the layers multiply by)."""
    x = jnp.asarray(a).astype(cfg.dtype)
    return jnp.swapaxes(x, -1, -2) if transpose else x


def swiglu_matrices(get, cfg, prefix: str,
                    names: Sequence[str] = ("gate_proj", "up_proj",
                                            "down_proj"),
                    experts: Optional[Tuple[int, int]] = None
                    ) -> Dict[str, Any]:
    """A SwiGLU's three published ``[out, in]`` matrices
    ``<prefix><name>.weight`` (``names``: gate, up, down) as the ``gate_up
    [H, 2I]`` and ``down [I, H]`` the layers multiply by.  With ``experts =
    (first, count)``: those experts' ``<prefix><e>.<name>.weight`` (an
    expert is a tensor of its own, so only the ones held are read), stacked
    on the host and transposed on the device, ``[count, H, 2I]`` and
    ``[count, I, H]``."""
    def read(name: str) -> np.ndarray:
        if experts is None:
            return get(f"{prefix}{name}.weight")
        return np.stack([get(f"{prefix}{e}.{name}.weight")
                         for e in range(experts[0], sum(experts))])

    gate, up, down = (read(name) for name in names)
    return {"gate_up": jnp.concatenate([on_device(cfg, gate, True),
                                        on_device(cfg, up, True)], -1),
            "down": on_device(cfg, down, True)}
