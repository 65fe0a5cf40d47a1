"""The mistral4 family's rehearsal: `tiny-mistral4.serve` end to end on the CPU
through run.py, and the comparison's control at the tiny size. Run with the
suite through tests/test_mistral4.py, or alone:
`JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_mistral4_rehearsal.py -q`.
"""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
TINY = "tiny-mistral4"


def test_the_rehearsal_cell_runs_end_to_end_on_the_cpu(tmp_path):
    """`tiny-mistral4.serve` through run.py: the `tony serve` path, the router,
    the replica registered through the family's hook (its prefill shape warmed
    before the server starts), chunked prefill and decode through the paged
    latent pool under the interpreter, two shared prefixes of three whole pages
    in the traffic, and the harness's own comparison with the reference:
    `correct`, with pages shared inside the window."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT, "HOME": str(tmp_path), "TMPDIR": str(tmp_path)}
    proc = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"), "--workload", TINY + ".serve",
                           "--seed", str(2 ** 31 + 46), "--seconds", "3", "--trace", "0"],
                          cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] >= 2, proc.stdout[-3000:]
    assert last["device"]["platform"] == "cpu" and "serve_out_tok_s" in last["metrics"]
    assert "repeated greedy request identical: True" in proc.stdout
    ctl = os.path.join(ROOT, ".bench_work", TINY + ".serve", "out", "ctl")
    with open(os.path.join(ctl, "snap.close.json")) as f:
        closed = {m["name"]: m["samples"] for m in json.load(f)["metrics"]}
    assert closed["tony_serve_prefix_hit_tokens_total"][0]["value"] >= 48          # a document's three pages, attached
    assert {s["labels"]["kind"] for s in closed["tony_serve_pages_total"]} == {"shared", "fresh"}


def test_the_control_lies_far_from_the_reference_and_the_program_on_it():
    """The float8 control teacher-forced through the float32 reference reads a
    gap where the float32 program reads none: what the cell's limit has to tell apart."""
    sys.path.insert(0, BENCH)
    try:
        import check
        import spec
        from chipside import seed_weights

        sizes = spec.model_sizes(spec.config(TINY), "serve-1chip")
        params = seed_weights(sizes, 11)
        import jax.numpy as jnp
        import numpy as np

        R = __import__("families").reference(sizes)
        prompt = np.random.default_rng(5).integers(1, sizes["vocab"], 40).tolist()
        toks = []
        import jax

        forward = jax.jit(lambda p, seq: R.forward(p, seq, sizes, "f32", 96))
        for _ in range(24):     # the reference's own greedy continuation: what a sound program returns
            seq = jnp.asarray(prompt + toks + [0] * (96 - len(prompt) - len(toks)), jnp.int32)
            toks.append(int(np.asarray(forward(params, seq))[len(prompt) + len(toks) - 1].argmax()))
        got = check.check_serve(params, sizes, [{"prompt": prompt, "tokens": toks}], control=True, pad_seq=96, pad_answer=32)
        assert got["worst_gap"] < 1e-5 and got["control_worst_gap"] > 0.01 and got["argmax_agree"] == 24
    finally:
        sys.path.remove(BENCH)
