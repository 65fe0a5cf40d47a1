"""The solar_open2 family's rehearsal: `tiny-solar-open2.serve` end to end on
the CPU through run.py, and the comparison's control at the tiny size. Run with
the suite through tests/test_solar_open2.py, or alone:
`JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_solar_open2_rehearsal.py -q`.
"""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
TINY = "tiny-solar-open2"


def test_the_rehearsal_cell_runs_end_to_end_on_the_cpu(tmp_path):
    """`tiny-solar-open2.serve` through run.py: the `tony serve` path, the
    router, the replica registered through the family's hook, chunked prefill in
    buckets of a page times a power of two, decode through the paged pool over
    the attention layers and the carried channel-gated state under the interpreter,
    a routed FFN in every layer of which the replica holds half, and the
    harness's own comparison with the reference: `correct`, with the four expert
    counters (the touched experts among them) moving inside the window and no
    prefix hit. Its own time limit: 300 s."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT, "HOME": str(tmp_path), "TMPDIR": str(tmp_path)}
    proc = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"), "--workload", TINY + ".serve",
                           "--seed", str(2 ** 31 + 57), "--seconds", "3", "--trace", "0"],
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
    rows, choices, touched = (moved(f"tony_serve_{k}_total") for k in ("expert_rows", "expert_choices", "experts_touched"))
    # held_share_pct.serve over the window, as readers/registry_delta reads it: 4 of 8 held, so about 50
    assert choices > 0 and 25.0 < 100.0 * rows / choices < 75.0 and 0 < touched <= rows, (rows, choices, touched)
    assert moved("tony_serve_prefix_hit_tokens_total") == 0 and moved("tony_serve_prefill_tokens_total") > 0


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
        # the seeded model's logits are of size 4: the float8 control moves them by whole units
        assert result["worst_gap"] < 1e-6 and result["argmax_agree"] == 8
        assert result["control_worst_gap"] > 1e-3, result
    finally:
        sys.path.remove(BENCH)
