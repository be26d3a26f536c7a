"""Batched decode serving: prefill + KV-cache decode loop with batching.

Serves a smoke-sized LM: requests arrive with prompts, get batched, prefilled
(full forward populates nothing here — decode replays the prompt token by
token to fill the cache, which is exact for these lengths), then decoded
greedily for N tokens per request.  The serve step is
``launch.steps.make_serve_step``, the one ``launch/serve.py`` runs.

  PYTHONPATH=src python examples/serve.py
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.launch.steps import make_serve_step
from repro.models import model as M


def main():
    cfg = configs.get_smoke("llama3_2_1b")
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, key)

    batch, max_len, gen_len = 4, 96, 24
    serve = jax.jit(make_serve_step(cfg), donate_argnums=(1,))

    # --- batched requests (different prompt lengths, left-aligned) ---
    rng = np.random.default_rng(0)
    prompt_lens = [8, 12, 5, 9]
    prompts = [rng.integers(0, cfg.vocab, (L,)).astype(np.int32)
               for L in prompt_lens]

    cache = M.init_cache(cfg, batch, max_len)
    # Prefill by stepping the prompts through the decode path (batched;
    # shorter prompts pad with token 0 and get overwritten by generation).
    maxp = max(prompt_lens)
    padded = np.zeros((batch, maxp), np.int32)
    for i, p in enumerate(prompts):
        padded[i, :len(p)] = p

    t0 = time.perf_counter()
    tok = jnp.asarray(padded[:, :1])
    out_tokens = [[] for _ in range(batch)]
    for pos in range(maxp + gen_len - 1):
        nxt, cache = serve(params, cache, tok, jnp.int32(pos))
        if pos + 1 < maxp:
            # still consuming prompts: teacher-force next prompt column
            tok = jnp.asarray(padded[:, pos + 1:pos + 2])
        else:
            tok = nxt[:, :, 0] if cfg.n_codebooks else nxt
            for i in range(batch):
                out_tokens[i].append(int(np.asarray(tok)[i, 0]))
    dt = time.perf_counter() - t0

    total_steps = maxp + gen_len - 1
    print(f"served {batch} requests, {total_steps} decode steps in "
          f"{dt:.2f}s ({dt/total_steps*1e3:.1f} ms/step batched)")
    for i in range(batch):
        print(f"req{i} (prompt {prompt_lens[i]} toks) -> "
              f"{out_tokens[i][:12]}...")


if __name__ == "__main__":
    main()
