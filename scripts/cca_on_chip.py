"""What PR 45's builder ran on the chip for ZAYA1-8B beside the benchmark's
cell (PERF.md section 6):

    chiprun -- python3 scripts/cca_on_chip.py
    chiprun -- python3 scripts/cca_on_chip.py --round-state bfloat16

The configuration's published widths over 2 of its layers (``--layers``; every
layer is the same kind: compressed convolutional attention, then top-1 of 16
experts behind the router network with its carry) and the whole tied 262
272-row table, float32 at the highest matmul precision: a prompt prefilled in
two slices (1024 + 512) through the GQA page pool and the slots' tails, then 8
paged decode steps, every row's logits against the plain reference's full
forward (the convolutions as sums over two positions, no cache, an expert at a
time) on the same seeded weights. This is what shows that the program's
mathematics is the model's; the benchmark's cell then runs it in bfloat16.

``--round-state`` is the control: the TAIL is rounded to that type after every
call, as a pool that kept it so would, and the same comparison has to read far
above the sound one (the cell's ``correct`` compares served tokens and cannot
tell the two apart). The comparison itself is scripts/kda_on_chip.py's.
``--tiny`` rehearses on the CPU at test sizes (pipe its output through ``grep
^CCA``)."""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _model(tiny: bool, layers: int, dtype: str):
    from benchmarks import common
    from benchmarks.runners import serve_cca

    config = common.load_json(os.path.join(
        common.ROOT, "benchmarks", "configs", "zaya1-8b.json"))
    config = {**config, "torch_dtype": dtype,
              "serve": {**config["serve"], "num_hidden_layers": layers}}
    if tiny:
        config.update(
            hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, moe_intermediate_size=32, vocab_size=512,
            num_experts=8, router_hidden_size=16)
    model = serve_cca.model_dict(config, "serve")
    return model, serve_cca.program_config(model)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--round-state", default="",
                   help="round the tail to this type after every call")
    p.add_argument("--seed", type=int, default=20261005)
    p.add_argument("--tiny", action="store_true")
    args = p.parse_args(argv)
    from oim_tpu.cli.common import init_jax

    init_jax("cpu" if args.tiny else "tpu")
    import kda_on_chip
    from benchmarks import weights_zaya as weights
    from benchmarks.reference import zaya_like as ref

    model, cfg = _model(args.tiny, args.layers, "float32")
    print("CCA " + json.dumps(kda_on_chip.agree(
        args, model, cfg, weights, ref, cfg.cca.tail_leaf)), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    sys.exit(main())
