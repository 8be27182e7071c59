"""Ice Wedge Polygons use case (paper §III-B): tiling + inference.

Each very-high-resolution "satellite image" is processed in two stages with
different resource shapes — exactly the paper's heterogeneous pattern:
  tiling    — CPU-slot Python function: split into 360x360 tiles;
  inference — SPMD function on a device sub-mesh: a small conv net scores
              every tile (the paper's GPU stage), tiles sharded over the
              task's private mesh.

Many images flow through concurrently; per-image dataflow edges are futures.

    PYTHONPATH=src python examples/iwp_pipeline.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import (DataFlowKernel, PilotDescription, RPEXExecutor,
                        python_app, spmd_app)

TILE = 90          # reduced 360 -> 90 for the CPU container
TILES_PER_IMG = 8


@python_app
def load_and_tile(image_id):
    """Stage 1 (CPU): load the scene and cut it into tiles."""
    rng = np.random.default_rng(image_id)
    scene = rng.standard_normal((TILE * 2, TILE * 4)).astype("float32")
    tiles = (scene.reshape(2, TILE, 4, TILE).transpose(0, 2, 1, 3)
             .reshape(TILES_PER_IMG, TILE, TILE))
    return {"image_id": image_id, "tiles": tiles}


@spmd_app(slots=4, mesh=(4, 1), jit=False)
def infer(mesh, payload):
    """Stage 2 (accelerator sub-mesh): score tiles, sharded over 'data'."""
    tiles = jnp.asarray(payload["tiles"])          # (8, T, T)
    kernel = jnp.ones((5, 5)) / 25.0

    def per_shard(t):                              # t: (2, T, T) local tiles
        sm = jax.vmap(lambda im: jax.scipy.signal.convolve2d(
            im, kernel, mode="same"))(t)
        score = jax.nn.sigmoid(sm.mean(axis=(1, 2)))
        return score

    f = shard_map(per_shard, mesh=mesh,
                      in_specs=P("data"), out_specs=P("data"))
    return {"image_id": payload["image_id"],
            "scores": np.asarray(f(tiles))}


@python_app
def collect(results):
    found = {r["image_id"]: float(np.max(r["scores"])) for r in results}
    return found


def main(n_images=12):
    rpex = RPEXExecutor(PilotDescription(n_slots=8))
    t0 = time.time()
    with DataFlowKernel(executors={"rpex": rpex}):
        per_image = [infer(load_and_tile(i)) for i in range(n_images)]
        summary = collect(per_image).result()
    rpex.shutdown()
    print(f"[iwp] {n_images} images in {time.time()-t0:.1f}s; "
          f"max polygon scores: "
          f"{ {k: round(v, 3) for k, v in list(summary.items())[:4]} } ...")
    assert len(summary) == n_images
    return summary


if __name__ == "__main__":
    main()
