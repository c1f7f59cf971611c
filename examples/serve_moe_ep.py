"""Expert-parallel MoE serving in ~40 lines (reference: DS-Inference MoE,
``deepspeed.init_inference(..., moe related kwargs)`` building expert-parallel
groups at serve time).

A Mixtral-family model serves with its stacked expert weights sharded over
the ``expert`` mesh axis — each device group holds 1/ep_size of them instead
of a full replica: whole experts, or from 1024 intermediate columns a group
on (Mixtral-8x7B's widths) a slice of every expert's columns, as
``deepspeed_tpu.models.mixtral.expert_layout`` says — while attention is
tensor-parallel over ``model``. Runs anywhere:

    # laptop / CI: virtual 8-device CPU mesh (ep=4 x mp=2)
    python examples/serve_moe_ep.py --cpu_devices 8

    # real TPU slice: drop the flag
    python examples/serve_moe_ep.py --ep 8
"""

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu_devices", type=int, default=0)
    ap.add_argument("--ep", type=int, default=4)
    ap.add_argument("--mp", type=int, default=2)
    ap.add_argument("--max_new_tokens", type=int, default=16)
    args = ap.parse_args()

    if args.cpu_devices:
        from deepspeed_tpu.utils.jax_compat import force_cpu_devices

        force_cpu_devices(args.cpu_devices)
    import jax  # noqa: F401 (platform must be pinned before first use)

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import MixtralConfig, MixtralForCausalLM

    cfg = MixtralConfig.tiny()  # swap for MixtralConfig.mixtral_8x7b()
    model = MixtralForCausalLM(cfg)
    ids = np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 8))
    params = model.init(jax.random.PRNGKey(0),
                        np.asarray(ids))["params"]

    engine = ds.init_inference(model, params=params, dtype="bf16",
                               mp_size=args.mp, ep_size=args.ep)
    w1 = engine.params["model"]["layers"]["block"]["block_sparse_moe"]["w1"]
    from deepspeed_tpu.models.mixtral import expert_layout

    E, inter, ep = (cfg.num_local_experts, cfg.intermediate_size,
                    engine.ep_world_size)
    print(f"expert shard spec: {w1.sharding.spec} (E={E}, I={inter}, ep={ep}: "
          f"layout {expert_layout(E, inter, ep)!r})")
    toks = engine.generate(ids, max_new_tokens=args.max_new_tokens,
                           do_sample=False)
    print("generated:", np.asarray(toks)[:, :8], "...")


if __name__ == "__main__":
    main()
