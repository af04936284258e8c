"""Serving driver: prefill a batch of prompts, then batched decode.

CPU-scale usage:
  PYTHONPATH=src python -m repro.launch.serve --arch glm4-9b --smoke \\
      --prompt-len 16 --new-tokens 8 --batch 2
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ShapeConfig, get_config
from repro.configs.smoke import smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.models.model import build_model
from repro.models.modules import init_params


def generate(cfg, *, batch: int, prompt_len: int, new_tokens: int) -> dict:
    """Prefill a batch of random prompts, replay them token by token
    through the decode path, then decode ``new_tokens`` greedily.

    Weights and prompts are random, from fixed seeds. Returns the
    prefill logits at the last prompt position, the decode path's logits
    at that same position (the replay), and the generated tokens
    [batch, new_tokens].
    """
    bundle = build_model(cfg)
    params = init_params(bundle.param_defs, jax.random.key(0))
    rng = np.random.default_rng(0)

    shape = ShapeConfig("serve", prompt_len, batch, "prefill")
    inputs = init_params(bundle.batch_defs(shape), jax.random.key(1))
    if "tokens" in inputs:
        inputs["tokens"] = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (batch, prompt_len)), jnp.int32)
    if "frames" in inputs:
        inputs["frames"] = jnp.asarray(
            rng.normal(size=inputs["frames"].shape), cfg.compute_dtype)

    prefill = jax.jit(make_prefill_step(bundle))
    decode = jax.jit(make_decode_step(bundle))
    logits, _ = prefill(params, inputs)
    # fresh cache sized for the full generation (prefill replayed into it)
    cache = init_params(bundle.cache_defs(batch, prompt_len + new_tokens),
                        jax.random.key(2))
    dec_batch = {"token": inputs["tokens"][:, :1] if "tokens" in inputs
                 else jnp.zeros((batch, 1), jnp.int32)}
    if "frames" in inputs:
        dec_batch["frames"] = inputs["frames"]
    # replay prompt tokens through the decode path, then sample greedily
    toks = []
    replay = None
    for t in range(prompt_len + new_tokens - 1):
        if "tokens" in inputs and t < prompt_len:
            dec_batch["token"] = inputs["tokens"][:, t:t + 1]
        lg, cache = decode(params, cache, dec_batch)
        if t == prompt_len - 1:
            replay = lg
        nxt = jnp.argmax(lg[:, 0, :], axis=-1).astype(jnp.int32)[:, None]
        if t >= prompt_len - 1:
            toks.append(np.asarray(nxt[:, 0]))
            dec_batch["token"] = nxt
    gen = np.stack(toks, 1) if toks else np.zeros((batch, 0), np.int32)
    return {"prefill_logits": logits, "replay_logits": replay,
            "tokens": gen}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--batch", type=int, default=2)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    gen = generate(cfg, batch=args.batch, prompt_len=args.prompt_len,
                   new_tokens=args.new_tokens)["tokens"]
    print(f"{cfg.name}: generated {gen.shape[1]} tokens/seq")
    for b in range(args.batch):
        print(f"  seq{b}: {gen[b].tolist()}")


if __name__ == "__main__":
    main()
