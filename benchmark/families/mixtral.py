"""The mixtral family: the llama family's decoder with a routed FFN (softmax
router, top-k gates renormalised), through `tony_tpu/models/mixtral.py`. It
shares the llama family's reference and counts, which read `experts` and
`top_k` from the sizes. No JAX at the top level.
"""

from __future__ import annotations

from families import llama

REFERENCE = llama.REFERENCE
COUNTS = llama.COUNTS
EXPERTS = ("num_local_experts", "num_experts_per_tok")


def sizes(cfg: dict, deployment: str) -> dict:
    dense = {k: v for k, v in cfg.items() if k not in EXPERTS}
    return {**llama.sizes(dense, deployment), "experts": int(cfg["num_local_experts"]),
            "top_k": int(cfg["num_experts_per_tok"])}


def program(sizes: dict, max_seq: int):
    from tony_tpu.models import mixtral

    return mixtral, mixtral.config_from_dict(
        {**llama.program_fields(sizes, max_seq), "num_experts": sizes["experts"], "top_k": sizes["top_k"]})


def serve_install(sizes: dict, bench: dict) -> None:
    raise SystemExit("serving_http builds llama-family weights only: a Mixtral replica waits for the program")
