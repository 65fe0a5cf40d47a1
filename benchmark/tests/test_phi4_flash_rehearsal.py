"""The phi4_flash family's rehearsal: `tiny-phi4-flash.serve` end to end on the CPU
through run.py, and the comparison's control at the tiny size. Run with the suite
through tests/test_phi4_flash.py, or alone:
`JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_phi4_flash_rehearsal.py -q`.
"""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
TINY = "tiny-phi4-flash"


def test_the_rehearsal_cell_runs_end_to_end_on_the_cpu(tmp_path):
    """`tiny-phi4-flash.serve` through run.py: the `tony serve` path, the router, the
    replica registered through the family's hook, chunked prefill in buckets of a
    page times a power of two with ONE row a chunk through the cross-decoder,
    decode through the one layer's pages, the rings and the carried states under
    the interpreter, and the harness's own comparison with the reference (which
    runs every layer on every row): `correct`, with the counters the rooflines
    and `cross_rows_pct.serve` read (context, prefill rows, causal pairs, cross
    rows) moving inside the window, no prefix hit and no routed FFN. Its own time
    limit: 300 s."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT, "HOME": str(tmp_path), "TMPDIR": str(tmp_path)}
    proc = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"), "--workload", TINY + ".serve",
                           "--seed", str(2 ** 31 + 63), "--seconds", "3", "--trace", "0"],
                          cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] >= 3, proc.stdout[-3000:]
    assert last["device"]["platform"] == "cpu" and "serve_out_tok_s" in last["metrics"]
    assert "repeated greedy request identical: True" in proc.stdout
    ctl = os.path.join(ROOT, ".bench_work", TINY + ".serve", "out", "ctl")
    snaps = []
    for tag in ("open", "close"):
        with open(os.path.join(ctl, f"snap.{tag}.json")) as f:
            snaps.append({m["name"]: m["samples"] for m in json.load(f)["metrics"]})
    total = lambda snap, name: sum(s["value"] for s in snap.get(name, []))
    moved = lambda name: total(snaps[1], name) - total(snaps[0], name)
    rows, pairs, context = (moved(f"tony_serve_{k}_total") for k in ("prefill_tokens", "prefill_pairs", "context_tokens"))
    # a prompt of 16 to 90 rows sees at least its own causal half and at most 90 x 91 / 2 pairs
    assert rows > 0 and rows * 17 / 2 <= pairs <= rows * 91 / 2 and context > 0, (rows, pairs, context)
    # one row a chunk through the cross-decoder: chunks of at most 32 rows, a last one padded to 16 or 32
    assert moved("tony_serve_cross_rows_total") == moved("tony_serve_prefill_chunks_total") and rows / 32 <= moved("tony_serve_cross_rows_total") <= rows / 16
    assert moved("tony_serve_prefix_hit_tokens_total") == 0 and moved("tony_serve_expert_rows_total") == 0


def test_the_control_lies_far_from_the_reference_and_the_program_on_it():
    """The float8 control teacher-forced through the float32 reference reads a
    gap where the float32 program reads none: what the cell's limit has to tell apart."""
    sys.path.insert(0, BENCH)
    try:
        import jax
        import jax.numpy as jnp
        import numpy as np

        import check
        import spec
        from chipside import seed_weights

        sizes = spec.model_sizes(spec.config(TINY), "serve-1chip")
        params = seed_weights(sizes, 11)
        R = __import__("families").reference(sizes)
        prompt = np.random.default_rng(5).integers(1, sizes["vocab"], 40).tolist()
        forward = jax.jit(lambda p, seq: R.forward(p, seq, sizes, "f32", 32))
        seq, toks = list(prompt), []
        for _ in range(8):                                                             # the reference's own greedy answer
            logits = np.asarray(forward(params, jnp.asarray(seq + [0] * (96 - len(seq)), jnp.int32)))[len(seq) - 1]
            toks.append(int(np.argmax(logits)))
            seq.append(toks[-1])
        result = check.check_serve(params, sizes, [{"prompt": prompt, "tokens": toks}], control=True, pad_seq=32, pad_answer=16)
        # the seeded model's logits are of size one: the control's choices lie far under the reference's best
        assert result["worst_gap"] < 1e-5 and result["argmax_agree"] == 8
        assert result["control_worst_gap"] > 1e-2, result
    finally:
        sys.path.remove(BENCH)
