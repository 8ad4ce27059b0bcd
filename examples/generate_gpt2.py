#!/usr/bin/env python
"""GPT-2 text generation with the paged KV cache.

Usage: JAX_PLATFORMS=cpu python examples/generate_gpt2.py --new-tokens 16
(--size 774m on a TPU; weights are randomly initialized unless --params
points at a checkpoint saved with save_parameters)."""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _common  # noqa: F401,E402  (repo path + compile cache)

import numpy as np


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--size", default="tiny",
                   choices=["tiny", "small", "medium", "774m", "xl"])
    p.add_argument("--params", default="", help=".params file to load")
    p.add_argument("--batch", type=int, default=2)
    p.add_argument("--prompt-len", type=int, default=8)
    p.add_argument("--new-tokens", type=int, default=16)
    p.add_argument("--sample", action="store_true")
    p.add_argument("--top-k", type=int, default=40)
    p.add_argument("--temperature", type=float, default=0.9)
    args = p.parse_args()

    import mxnet_tpu as mx
    from mxnet_tpu.models import (GPT2Config, GPT2ForCausalLM,
                                  gpt2_774m_config, gpt2_medium_config,
                                  gpt2_small_config, gpt2_xl_config)

    if args.size == "tiny":
        cfg = GPT2Config(vocab_size=512, units=64, num_layers=2,
                         num_heads=2, max_length=256, dropout=0.0,
                         attention_dropout=0.0)
    else:
        cfg = {"small": gpt2_small_config, "medium": gpt2_medium_config,
               "774m": gpt2_774m_config, "xl": gpt2_xl_config}[args.size](
            dropout=0.0, attention_dropout=0.0)
    net = GPT2ForCausalLM(cfg)
    net.initialize(mx.init.Normal(0.02))
    if args.params:
        net.load_parameters(args.params)

    rng = np.random.default_rng(0)
    prompt = mx.nd.array(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)),
        dtype="int32")
    t0 = time.time()
    out = net.generate(prompt, args.new_tokens, do_sample=args.sample,
                       top_k=args.top_k if args.sample else None,
                       temperature=args.temperature, paged=True,
                       page_size=64)
    toks = out.asnumpy()
    dt = time.time() - t0
    print(f"{args.batch * args.new_tokens} tokens in {dt:.2f}s "
          f"({args.batch * args.new_tokens / dt:.1f} tok/s, first call "
          "includes compile)")
    for row in toks:
        print("generated ids:", row.tolist())


if __name__ == "__main__":
    main()
