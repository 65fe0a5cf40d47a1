"""Two decode chunks in flight against one: `ContinuousBatcher.step` dispatches
the next chunk before it reads the last one's tokens, and everything a client
can see has to be what the serial engine gave: every request's tokens, what
`drain_stream` hands out pass by pass, and the cache's lengths after a drain.

The serial engine is kept HERE (`serial_pass`): one chunk, admission behind it,
wait, emit, return, from the engine's own building blocks in the order
`step` had them before. Both engines run one script of submissions and
cancellations, pass by pass, over the same weights.
"""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from tony_tpu.models import registry, serving
from tony_tpu.models.serving import ContinuousBatcher


def serial_pass(eng) -> bool:
    """The pass of the engine before two chunks flew: dispatch ONE chunk, do the
    next chunk's admission behind it, block on its tokens, emit, return."""
    chunk = eng._dispatch_chunk() if eng.running else None
    eng.phase.to("admit")
    eng._flush_retired()
    eng._admit("chunk" if chunk else "idle")
    if chunk:
        eng._emit(chunk)
    more = bool(eng.running or eng.pending or eng._staged)
    if not more:
        eng._flush_retired()
    eng.phase.to(None)
    return more


@dataclasses.dataclass
class Script:
    """What the callers do. `submit[p]`: the requests (label, prompt length,
    budget) that arrive before pass p; `cancel[p]`: the labels cancelled before
    pass p; `again`: after the engine has drained, the whole script once more
    on the same engine."""

    submit: dict
    cancel: dict = dataclasses.field(default_factory=dict)
    again: bool = False


def prompt(label: str, n: int, vocab: int) -> list[int]:
    return np.random.default_rng(sum(label.encode())).integers(1, vocab, n).tolist()


def play(eng, one_pass, script: Script, vocab: int) -> dict:
    """Run the script to the end as the server's loop does (intake, a pass,
    `drain_stream` with the finished popped from `done`) and keep what a
    client sees."""
    rounds, passes, streams, tokens = (2 if script.again else 1), [], {}, {}
    for r in range(rounds):
        rid_of, p, more = {}, 0, True
        while more or any(q >= p for q in script.submit):
            for label, n, budget in script.submit.get(p, ()):
                rid_of[label] = eng.submit(prompt(label, n, vocab), budget)
            for label in script.cancel.get(p, ()):
                assert eng.cancel(rid_of[label]), f"{label} was not there to cancel before pass {p}"
            more = one_pass(eng)
            label_of = {rid: f"{label}.{r}" for label, rid in rid_of.items()}
            seen = {}
            for rid, (toks, done) in eng.drain_stream().items():
                final = eng.done.pop(rid, None) if done else None
                seen[label_of[rid]] = (toks, done)
                streams.setdefault(label_of[rid], []).append((toks, done))
                if done:
                    tokens[label_of[rid]] = final
            passes.append(seen)
            p += 1
            assert p < 200, "the engine does not drain"
    while passes and not passes[-1]:
        passes.pop()  # an EOS met in the last chunk but one leaves the chunk behind it to be read: a pass that hands out nothing
    out = {"passes": passes, "streams": streams, "tokens": tokens, "lengths": np.asarray(eng.cache.lengths).tolist(),
           "slot_len": list(eng._slot_len), "running": dict(eng.running), "chunks_left": list(eng._chunks),
           "retired_left": list(eng._retired_slots)}
    if eng.kv == "paged":
        out["live_pages"] = eng.allocator.live_pages()
    return out


LLAMA = dict(preset="tiny", kw=dict(max_len=128, kv="paged", page_len=16))
# every family's tiny configuration, as docs and .claude/skills/verify name its engine
FAMILY = {
    "llama": LLAMA,
    "exaone_moe": dict(preset="exaone-moe-tiny", kw=dict(max_len=128, kv="paged", page_len=16, prefill_chunk=32)),
    "minicpm_sala": dict(preset="sala-tiny", kw=dict(max_len=128, kv="paged", page_len=8, prefill_chunk=32)),
    "dots3_note": dict(preset="dots3-note-tiny", kw=dict(max_len=128, kv="paged", page_len=16, prefill_chunk=32)),
    "mistral4": dict(preset="mistral4-tiny", kw=dict(max_len=128, kv="paged", page_len=16, prefill_chunk=32)),
    "olmo_hybrid": dict(preset="olmo-hybrid-tiny", kw=dict(max_len=256, kv="paged", page_len=16, prefill_chunk=32)),
    "granite_hybrid": dict(preset="granite-hybrid-tiny", kw=dict(max_len=128, kv="paged", page_len=16, prefill_chunk=32)),
    "solar_open2": dict(preset="solar-open2-tiny", kw=dict(max_len=128, kv="paged", page_len=16, prefill_chunk=32)),
    "falcon_h1": dict(preset="falcon-h1-tiny", kw=dict(max_len=128, kv="paged", page_len=16, prefill_chunk=32)),
}
#: the families whose engine carries a state a slot beside its pages (tests/test_engine_pipeline_recurrent.py)
RECURRENT = {"minicpm_sala", "olmo_hybrid", "granite_hybrid", "solar_open2", "falcon_h1"}
# two slots, chunks of 4. L decodes throughout; A's budget ends inside chunk 2 (1 + 4 + 1), B waits for its slot and
# is inserted behind that chunk, C arrives while three decode, D after two passes with nothing to do but decode
MIX = Script({0: [("L", 20, 30), ("A", 9, 6), ("B", 12, 5)], 3: [("C", 7, 3)], 6: [("D", 18, 7)]})
CASES = {
    **{f"family-{name}": dict(model=model, script=MIX) for name, model in FAMILY.items()},
    "dense-cache": dict(model=dict(preset="tiny", kw=dict(max_len=128, kv="dense")), script=MIX),
    "budget-ends-mid-chunk": dict(model=LLAMA, script=Script({0: [("A", 5, 3), ("B", 9, 6)], 1: [("C", 6, 7)], 2: [("D", 6, 2)]})),
    "eos-mid-chunk": dict(model=LLAMA, eos_of=("A", 2), script=MIX),
    "eos-ends-the-last-request": dict(model=LLAMA, eos_of=("L", 6), script=Script({0: [("L", 20, 30)]})),
    "cancel-with-two-chunks-in-flight": dict(model=LLAMA, shifted=True, script=Script(
        {0: [("L", 20, 30), ("A", 9, 14), ("B", 12, 5)]}, cancel={2: ["A"]})),
    "cancel-in-the-last-chunk": dict(model=LLAMA, script=Script(
        {0: [("L", 20, 30), ("A", 9, 6)]}, cancel={2: ["A"]})),
    "refills-a-handed-back-slot": dict(model=LLAMA, script=Script(
        {0: [(label, 4 + i, 2 + i % 4) for i, label in enumerate("ABCDEFG")]})),
    "drain-to-empty-and-start-again": dict(model=LLAMA, script=dataclasses.replace(MIX, again=True)),
    "one-token-requests": dict(model=LLAMA, script=Script({0: [("A", 5, 1), ("B", 6, 1)], 1: [("C", 7, 1)], 2: [("D", 8, 9), ("E", 9, 1)]})),
    "sampled": dict(model=LLAMA, engine=dict(temperature=0.8, top_k=8), script=MIX),
}


@pytest.mark.parametrize("case", sorted(c for c in CASES if not c.startswith("family-")))
def test_two_chunks_in_flight_hand_out_what_the_serial_engine_did(case, monkeypatch):
    """The scripts, on the llama family; every family's engine under the one
    script MIX is tests/test_engine_pipeline_families.py and
    tests/test_engine_pipeline_recurrent.py (their compiles are most of this
    subject's time: files of their own for `--dist loadfile`)."""
    two_engines_hand_out_the_same(case, monkeypatch)


def two_engines_hand_out_the_same(case, monkeypatch):
    monkeypatch.setenv("TONY_PALLAS_INTERPRET", "1")
    spec = CASES[case]
    cfg = registry.presets()[spec["model"]["preset"]]
    if spec["model"]["preset"] == "tiny":
        cfg = dataclasses.replace(cfg, dtype="float32")  # no tied logits for a batch-mate to break another way
    params = registry.module_of(cfg).init(jax.random.PRNGKey(5), cfg)
    script, vocab = spec["script"], cfg.vocab_size
    kw = dict(num_slots=2, decode_chunk=4, **spec["model"]["kw"], **spec.get("engine", {}))
    if "eos_of" in spec:
        # a token the seeded model emits: the label's token of that index, from a run with no EOS
        label, i = spec["eos_of"]
        kw["eos_id"] = play(ContinuousBatcher(params, cfg, **kw), ContinuousBatcher.step, script, vocab)["tokens"][label + ".0"][i]

    ahead0, chunks0 = serving._CHUNKS_AHEAD.value(), serving._CHUNKS.value()
    got = play(ContinuousBatcher(params, cfg, **kw), ContinuousBatcher.step, script, vocab)
    ahead, chunks = serving._CHUNKS_AHEAD.value() - ahead0, serving._CHUNKS.value() - chunks0
    want = play(ContinuousBatcher(params, cfg, **kw), serial_pass, script, vocab)

    assert got["tokens"] == want["tokens"]
    assert got["streams"] == want["streams"]
    if not spec.get("shifted"):
        # (a cancel reaches the device one chunk later than it did: whoever waits for that slot streams the same, a pass later)
        assert got["passes"] == want["passes"]
    for key in ("lengths", "slot_len", "running", "chunks_left", "retired_left", "live_pages"):
        assert got.get(key) == want.get(key), key
    assert not any(got["lengths"]) and not got["running"] and not got["chunks_left"]
    # what the script is there for
    cancelled = {f"{label}.0" for labels in script.cancel.values() for label in labels}
    budgets = {f"{label}.{r}": n for reqs in script.submit.values() for label, _, n in reqs for r in (0, 1)}
    assert set(got["tokens"]) == {k for k in budgets if k.endswith(".0") or script.again} - cancelled
    if "eos_of" in spec:
        short = [k for k, toks in got["tokens"].items() if len(toks) < budgets[k]]
        assert short and all(got["tokens"][k][-1] == kw["eos_id"] for k in short)
    else:
        assert all(len(toks) == budgets[k] for k, toks in got["tokens"].items())
    # every chunk but the first of a busy spell went out with the one before it unread
    assert ahead == chunks - (2 if script.again else 1)


def test_the_dense_rehearsal_is_correct_under_a_capture(tmp_path):
    """`tiny-dense.serve` through run.py with `--trace 1`: the `tony serve` path,
    the router, a replica whose engine keeps two chunks in flight while the
    profiler captures it, and every conjunct of `serve_cell.run`'s `correct`:
    the repeated request identical, no request failed, nothing left in flight,
    the fleet drained cleanly on its interrupt, the tokens the reference's."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": root, "HOME": str(tmp_path), "TMPDIR": str(tmp_path)}
    proc = subprocess.run([sys.executable, os.path.join(root, "benchmark", "run.py"), "--workload", "tiny-dense.serve",
                           "--seed", str(2 ** 31 + 60), "--seconds", "4", "--trace", "1"],
                          cwd=root, env=env, capture_output=True, text=True, timeout=170)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] >= 2, proc.stdout[-3000:]
    assert last["device"]["platform"] == "cpu"
    ctl = os.path.join(root, ".bench_work", "tiny-dense.serve", "out", "ctl")
    assert os.path.exists(os.path.join(ctl, "trace.done")), "no capture ran"

    def total(snap, name):
        return sum(s["value"] for m in json.load(open(os.path.join(ctl, snap)))["metrics"] if m["name"] == name
                   for s in m["samples"])

    chunks = total("snap.close.json", "tony_serve_engine_chunks_total") - total("snap.open.json", "tony_serve_engine_chunks_total")
    ahead = total("snap.close.json", "tony_serve_chunks_ahead_total") - total("snap.open.json", "tony_serve_chunks_ahead_total")
    assert 0 < ahead <= chunks, (ahead, chunks)
