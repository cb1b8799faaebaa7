"""ResNet-50 inference server (BASELINE.json config #5).

Image tensors arrive over the RPC plane (any transport — TCP, shm rings, or
stock gRPC clients via the h2 path), are decoded zero-copy, batched across
connections by the fan-in batcher, and classified by a jitted flax ResNet-50.

    python examples/resnet_server.py --port 50051 [--thin] [--batch 8]
    python examples/resnet_client.py --target 127.0.0.1:50051 --n 4
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_server(port: int = 0, thin: bool = False, batch: int = 8,
                 max_delay_s: float = 0.003):
    import jax
    import jax.numpy as jnp

    from tpurpc.jaxshim import serve_jax
    from tpurpc.models.resnet import (init_resnet, make_infer_fn,
                                      resnet18_thin, resnet50)

    size = 32 if thin else 224
    model = resnet18_thin(10) if thin else resnet50(1000)
    variables = init_resnet(jax.random.PRNGKey(0), model, image_size=size,
                            batch=1)
    infer = jax.jit(make_infer_fn(model))

    def handler(tree):
        logits = infer(variables, jnp.asarray(tree["images"]))
        return {"logits": logits,
                "top1": jnp.argmax(logits, axis=-1)}

    srv, bound, batcher = serve_jax(
        handler, f"0.0.0.0:{port}", name="Classify", batching=True,
        max_batch=batch, max_delay_s=max_delay_s)
    return srv, bound, batcher, size


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, default=50051)
    ap.add_argument("--thin", action="store_true",
                    help="small model/images for smoke runs")
    ap.add_argument("--batch", type=int, default=8)
    args = ap.parse_args()
    from tpurpc.utils import jaxenv

    jaxenv.enable_compile_cache()  # before first use of jax
    srv, port, _, size = build_server(args.port, args.thin, args.batch)
    print(f"ResNet server on :{port} (image size {size})", flush=True)
    srv.wait_for_termination()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
