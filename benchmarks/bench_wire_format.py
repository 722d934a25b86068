"""Beyond-paper: host→device wire-format bytes (uint8 + on-chip dequant vs
f32/bf16 on the host).

The paper minimizes host-side copies; we extend the idea across the wire:
transfer uint8 and run kernels/dequant_normalize on-chip.  This bench
measures actual bytes through the DeviceTransfer stage and the end-to-end
batch latency for each format.
"""

from __future__ import annotations

import tempfile
import time

import jax.numpy as jnp
import numpy as np

from repro.data import SyntheticImageDataset
from repro.data.codec import decode_sample, resize_nearest
from repro.data.transfer import DeviceTransfer
from repro.kernels.ops import dequant_normalize

N, HW = 48, (112, 112)
MEAN = jnp.array([0.485, 0.456, 0.406], jnp.float32)
STD = jnp.array([0.229, 0.224, 0.225], jnp.float32)


def run() -> list[tuple[str, float, str]]:
    rows = []
    with tempfile.TemporaryDirectory() as d:
        ds = SyntheticImageDataset.materialize(d, N, hw=(128, 128), seed=0)
        imgs = np.stack([resize_nearest(decode_sample(ds.read_bytes(i)), HW) for i in range(N)])

        # uint8 wire + on-chip dequant (ours)
        tr = DeviceTransfer()
        t0 = time.monotonic()
        out = tr({"images": imgs})
        x = dequant_normalize(out["images"], MEAN, STD)
        x.block_until_ready()
        dt8 = time.monotonic() - t0
        rows.append(("wire_uint8_dequant_onchip", dt8 * 1e6 / N, f"{tr.bytes_moved / 2**20:.1f}MB_moved"))

        # f32 host-side normalize (the conventional loader)
        tr32 = DeviceTransfer()
        t0 = time.monotonic()
        host = (imgs.astype(np.float32) / 255.0 - np.array([0.485, 0.456, 0.406], np.float32)) / np.array(
            [0.229, 0.224, 0.225], np.float32
        )
        out = tr32({"images": np.ascontiguousarray(host.transpose(0, 3, 1, 2))})
        out["images"].block_until_ready()
        dt32 = time.monotonic() - t0
        rows.append(("wire_f32_host_normalize", dt32 * 1e6 / N, f"{tr32.bytes_moved / 2**20:.1f}MB_moved"))

        ratio = tr32.bytes_moved / max(tr.bytes_moved, 1)
        rows.append(("wire_bytes_reduction", 0.0, f"x{ratio:.1f}_fewer_h2d_bytes_with_uint8"))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    for r in run():
        print(",".join(map(str, r)))
