"""Low-rank model compression with the distributed OOM t-SVD.

Factors every large 2-D weight of a trained checkpoint to rank r with the
paper's power method (out-of-core: weight matrices stream through in
blocks, so this works even when a single matrix exceeds device memory),
then reports the size/quality trade-off.

    PYTHONPATH=src python examples/compress_model.py --rank 16
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import svd
from repro.models import transformer as T
from repro.models.config import ModelConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rank", type=int, default=16)
    ap.add_argument("--min-dim", type=int, default=128)
    args = ap.parse_args()

    cfg = ModelConfig(name="demo", family="dense", num_layers=4,
                      d_model=256, num_heads=8, num_kv_heads=4, d_ff=1024,
                      vocab_size=4096, dtype="float32", scan_layers=False)
    params = T.init_model(jax.random.PRNGKey(0), cfg)

    flat, treedef = jax.tree.flatten_with_path(params)
    total_before = total_after = 0
    print(f"{'weight':<44} {'shape':>16} {'rank':>5} {'rel err':>9} {'ratio':>7}")
    new_leaves = []
    for path, w in flat:
        name = "/".join(str(p.key) if hasattr(p, "key") else str(p)
                        for p in path)
        arr = np.asarray(w, np.float32)
        mat = arr.reshape(-1, arr.shape[-1]) if arr.ndim > 2 else arr
        total_before += arr.size
        if (arr.ndim < 2 or min(mat.shape) < args.min_dim
                or args.rank >= min(mat.shape) // 2):
            new_leaves.append(w)
            total_after += arr.size
            continue
        # svd() dispatches on the input type: the largest matrices go in
        # as host numpy arrays (out-of-core streaming — the drop-in that
        # works when a weight exceeds device memory), the rest as device
        # arrays (serial block iteration, all ranks per pass).
        target = mat if mat.shape[0] >= 4096 else jnp.asarray(mat)
        res = svd(target, args.rank, method="block", n_blocks=4,
                  eps=1e-6, max_iters=50)
        rec = (np.asarray(res.U) * np.asarray(res.S)) @ np.asarray(res.V).T
        err = np.linalg.norm(mat - rec) / np.linalg.norm(mat)
        lr_size = args.rank * (mat.shape[0] + mat.shape[1] + 1)
        total_after += lr_size
        ratio = arr.size / lr_size
        print(f"{name:<44} {str(mat.shape):>16} {args.rank:>5} "
              f"{err:>9.3f} {ratio:>6.1f}x")
        new_leaves.append(jnp.asarray(rec.reshape(arr.shape)))

    print(f"\nmodel params: {total_before/1e6:.2f}M -> "
          f"{total_after/1e6:.2f}M  "
          f"({total_before/total_after:.2f}x smaller)")
    # the compressed model still runs
    new_params = jax.tree.unflatten(treedef, new_leaves)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 4096)
    logits, _ = T.forward(new_params, cfg, {"tokens": toks})
    assert bool(jnp.all(jnp.isfinite(logits)))
    print("compressed model forward pass: OK (finite logits)")


if __name__ == "__main__":
    main()
