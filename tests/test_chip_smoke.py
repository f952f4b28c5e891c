"""CPU rehearsal of chip_smoke.py's control flow.

The script is the repo's proof on the chip; here only its plumbing is
held: which phases run, what a failed phase does to the exit code, what
the last line carries, the reply-agreement criterion, the corpus that has
to reach the recipe's vocabulary. The device check is injected by the
tests (never by an option of the script); the phases themselves run at
toy size in the slow tier, interpret-mode kernels and all.
"""

import importlib.util
import json
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location(
    "chip_smoke", REPO / "chip_smoke.py"
)
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


@pytest.fixture(autouse=True)
def checkout(tmp_path, monkeypatch):
    """A stand-in checkout, so that ``main`` makes and removes its work
    directory under tmp_path and not in the repo."""
    (tmp_path / "checkout" / chip_smoke.PKG).mkdir(parents=True)
    monkeypatch.setattr(chip_smoke, "ROOT", tmp_path / "checkout")


def _run_main(argv, capsys, **inject):
    rc = chip_smoke.main(argv, **inject)
    return rc, capsys.readouterr().out.strip().splitlines()


def test_last_line_has_exactly_the_contract_keys(capsys):
    phases = []

    def run_child(phase, work):
        phases.append(phase)
        return {"device": V5E, "losses": [2.0, 1.0]}

    rc, lines = _run_main(
        [], capsys, run_child=run_child,
        serve_phase=lambda work, size, device: phases.append("serve"),
    )
    assert rc == 0 and phases == ["train", "jamba", "kv_write", "serve"]
    last = json.loads(lines[-1])
    assert last == {"ok": True, "device": V5E}
    assert list(last) == ["ok", "device"]
    assert list(last["device"]) == ["platform", "kind", "count"]


@pytest.mark.parametrize("failing", ["train", "serve"])
def test_a_failed_phase_is_a_nonzero_exit_and_no_result(capsys, failing):
    def run_child(phase, work):
        if failing == "train":
            raise chip_smoke.SmokeFailure("phase train exited 1")
        return {"device": V5E}

    def serve_phase(work, size, device):
        raise chip_smoke.SmokeFailure("2 engine restart(s)")

    rc, lines = _run_main([], capsys, run_child=run_child,
                          serve_phase=serve_phase)
    assert rc == 1
    assert not any('"ok"' in line for line in lines)
    assert "FAILED" in lines[-1]


def test_kv_write_phase_rehearsed_at_six_slots(tmp_path, capsys):
    """The phase's own control flow, through the interpreter: every
    leaf kind and every pattern of writing slots against NumPy, on the
    pool the call before gave back. What it is FOR (a kept grid step on
    the chip's pipeline) only the chip shows."""
    cpu = {"platform": "cpu", "kind": "cpu", "count": 1}
    got = chip_smoke.kv_write_phase(
        tmp_path, dict(chip_smoke.FULL, kv_slots=6), lambda what: cpu)
    leaves = chip_smoke.kv_write_leaves(6)
    assert got["device"] == cpu
    assert sorted(got["checked"]) == sorted(name for name, *_ in leaves)
    assert all(len(patterns) == 7 for patterns in got["checked"].values())
    assert {shape[axis] for _, shape, axis, _ in leaves} == {6}
    assert capsys.readouterr().out.count("bit for bit") == len(leaves)


def test_kv_write_patterns_cover_every_place_a_kept_step_can_stand():
    import numpy as np

    got = dict(chip_smoke.kv_write_patterns(
        256, 512, np.random.default_rng(0)))
    writers = {k: [b for b, t in enumerate(v) if t >= 0]
               for k, v in got.items()}
    assert all(len(v) == 256 and max(v) < 512 for v in got.values())
    assert writers["none"] == [] and writers["all"] == list(range(256))
    assert writers["last-only"] == [255] and writers["first-only"] == [0]
    assert writers["kept-then-writers"][0] == 85
    assert len(writers["one-in-eight"]) == 32
    a, b = writers["same-block"]
    assert a != b and (got["same-block"][a] // 128
                       == got["same-block"][b] // 128)


def test_multichip_runs_that_phase_alone_and_reports_four(capsys):
    phases = []

    def run_child(phase, work):
        phases.append(phase)
        return {"device": dict(V5E, count=4)}

    rc, lines = _run_main(
        ["--multichip"], capsys, run_child=run_child,
        serve_phase=lambda *a: phases.append("serve"),
    )
    assert rc == 0 and phases == ["multichip"]
    assert json.loads(lines[-1])["device"]["count"] == 4


def test_without_an_accelerator_it_fails_and_prints_no_result():
    """The script as the driver runs it, in this sandbox: JAX finds no
    TPU, the first child says so and exits, the parent follows."""
    r = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")], cwd=str(REPO),
        capture_output=True, text=True, timeout=300,
    )
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "JAX found no TPU" in r.stderr
    assert not (REPO / ".chip_smoke").exists()  # nothing left behind


def test_alone_in_a_directory_it_fails(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    lone.write_text((REPO / "chip_smoke.py").read_text())
    r = subprocess.run([sys.executable, str(lone)], cwd=str(tmp_path),
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0 and '"ok"' not in r.stdout


def _reply(tokens, logprobs, tops=None):
    return {"tokens": tokens, "token_logprobs": logprobs,
            "top_logprobs": tops or [[[t, lp]] for t, lp in
                                     zip(tokens, logprobs)]}


def test_replies_agree_criterion():
    agree, tol = chip_smoke.replies_agree, 0.25
    ref = _reply([5, 6, 7], [-1.0, -2.0, -3.0])
    # same tokens, log-probabilities within tolerance: the whole length
    assert agree(ref, _reply([5, 6, 7], [-1.1, -2.2, -2.9]), tol) == 3
    # same token, log-probabilities too far apart
    with pytest.raises(chip_smoke.SmokeFailure, match="apart"):
        agree(ref, _reply([5, 6, 7], [-1.0, -2.5, -3.0]), tol)
    # a fork at a near-tie ends the walk there...
    tie = [[[6, -2.0], [9, -2.1]]]
    a = _reply([5, 6], [-1.0, -2.0], [[[5, -1.0]]] + tie)
    b = _reply([5, 9], [-1.0, -2.0], [[[5, -1.0]]] + [[[9, -2.0], [6, -2.05]]])
    assert agree(a, b, tol) == 1
    # ...a fork that is no near-tie fails, at the first token too
    c = _reply([5, 9], [-1.0, -2.0], [[[5, -1.0]]] + [[[9, -2.0], [6, -4.0]]])
    with pytest.raises(chip_smoke.SmokeFailure, match="near-tie"):
        agree(a, c, tol)
    with pytest.raises(chip_smoke.SmokeFailure, match="near-tie"):
        agree(_reply([1], [-0.1]), _reply([2], [-0.1]), tol)


def test_every_pallas_variant_is_held_against_an_xla_one():
    variants = chip_smoke.serve_variants(8)
    names = [name for name, _, _ in variants]
    for name, flags, against in variants:
        if "pallas" in flags:
            # an earlier XLA server with the same KV dtype
            assert names.index(against) < names.index(name)
            ref_flags = variants[names.index(against)][1]
            assert "pallas" not in ref_flags
            assert ("int8" in flags) == ("int8" in ref_flags)
        else:
            assert against is None


def test_smoke_corpus_reaches_the_recipe_vocabulary(tmp_path):
    """``train()`` narrows the model to what BPE reached (499 on the
    stock synthetic corpus); the smoke's own corpus has to give 12,000."""
    from differential_transformer_replication_tpu.data.corpus import load_corpus
    from differential_transformer_replication_tpu.data.tokenizer import (
        train_bpe_tokenizer,
    )

    full = chip_smoke.FULL
    path = tmp_path / "corpus.txt"
    chip_smoke.write_corpus(path, full["corpus_docs"], full["corpus_words"],
                            chip_smoke.SEED)
    texts = load_corpus(str(path), full["corpus_docs"])
    assert len(texts) == full["corpus_docs"]
    tok = train_bpe_tokenizer(texts, full["vocab"], 2, None)
    assert tok.get_vocab_size() == full["vocab"] == 12000


TOY = dict(
    chip_smoke.FULL, n_layer=2, n_embd=64, n_head=2, block_size=32,
    micro_batch=4, vocab=500, dtype="float32", steps=6, eval_iters=2,
    warmup=2, lr=3e-3, corpus_docs=300, corpus_words=2000,
    prompt_lens=(20, 8, 4), new_tokens=8, num_slots=4, page_size=8,
    dp=4, dp_steps=4,
)


def _this_device(what):
    from differential_transformer_replication_tpu.utils.device import (
        device_summary,
    )

    return device_summary()


@pytest.mark.slow
def test_toy_rehearsal_of_the_one_chip_phases(tmp_path, monkeypatch):
    """train + reference + sync in-process, then every serve variant as
    a real server process, at toy size on the CPU."""
    monkeypatch.setattr(chip_smoke, "ROOT", REPO)  # servers start there
    # the servers are single-device programs: no 8-device flag for them
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    result = chip_smoke.train_phases(tmp_path, TOY, _this_device)
    assert result["vocab_size"] == 500
    assert result["losses"][-1] < result["losses"][0]
    device = dict(result["device"], count=1)
    chip_smoke.serve_phase(tmp_path, TOY, device)


@pytest.mark.slow
def test_toy_rehearsal_of_the_multichip_phase(tmp_path, monkeypatch):
    import jax

    # the phase wants exactly `dp` devices; the test mesh has eight
    monkeypatch.setattr(jax, "devices", lambda *a: jax.local_devices()[:4])
    result = chip_smoke.multichip_phase(
        tmp_path, TOY, lambda what: dict(_this_device(what), count=4)
    )
    assert result["dp_losses"] == pytest.approx(result["one_losses"],
                                                rel=1e-4)
