"""Serve a Llama model with continuous batching and a paged KV cache.

No weights ship in the image, so this serves a randomly-initialized tiny
Llama — the point is the serving mechanics: mixed-length requests stream
through `horovod_tpu.serving`, joining and leaving the running batch
independently, with per-request TTFT/throughput metrics at the end.

Run:  python examples/llama_serve.py [--requests 8] [--max-active 4]
      python examples/llama_serve.py --stream     # print tokens live
"""

import argparse
import os
import sys

# One XLA device when launched under a test rig whose XLA_FLAGS leak
# (see tf_keras_bert_pretrain.py); harmless standalone.
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
    " --xla_force_host_platform_device_count=1"

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-active", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=128)
    ap.add_argument("--stream", action="store_true",
                    help="print tokens as they are generated")
    ap.add_argument("--platform", default=None,
                    help="jax platform to pin (cpu/tpu); default: "
                         "whatever JAX finds")
    args = ap.parse_args()

    import jax
    if args.platform:
        jax.config.update("jax_platforms", args.platform)

    from horovod_tpu import serving
    from horovod_tpu.models import llama

    cfg = llama.LlamaConfig.tiny(vocab_size=512, d_model=128, n_layers=4,
                                 n_heads=8, n_kv_heads=4, d_ff=256)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))

    rng = np.random.RandomState(0)
    lens = [12, 48, 24, 96, 8, 64, 32, 16]
    budgets = [16, 8, 24, 12, 32, 8, 16, 24]

    stream_cb = None
    if args.stream:
        def stream_cb(rid, tok):
            print(f"  req{rid} -> {tok}")

    with serving.serve(params, cfg, block_size=args.block_size,
                       num_blocks=args.num_blocks,
                       max_active=args.max_active) as session:
        futs = []
        for i in range(args.requests):
            prompt = rng.randint(0, cfg.vocab_size,
                                 size=(lens[i % len(lens)],)).astype(np.int32)
            m = budgets[i % len(budgets)]
            futs.append(session.submit(prompt, m, stream_cb=stream_cb))
            print(f"submitted req{i}: prompt {len(prompt)} tokens, "
                  f"budget {m}")
        session.drain()

        print(f"\ndecode attention path: {session.engine.attention_path}"
              f" on {jax.devices()[0].device_kind}")
        print("per-request results:")
        failed = 0
        for fut in futs:
            r = fut.result()
            m = r.metrics
            if "error" in m:
                failed += 1
                print(f"  req{r.req_id}: FAILED after {m['new_tokens']} "
                      f"tokens: {m['error']}")
                continue
            print(f"  req{r.req_id}: {m['prompt_len']:3d} prompt + "
                  f"{m['new_tokens']:2d} new | queue "
                  f"{m['queue_wait_s'] * 1e3:6.1f} ms | ttft "
                  f"{m['ttft_s']:.3f}s | {m['decode_tokens_per_s'] or 0:.0f}"
                  f" tok/s | preemptions {m['preemptions']}")
    if failed:
        sys.exit(f"{failed} of {len(futs)} requests failed")


if __name__ == "__main__":
    main()
