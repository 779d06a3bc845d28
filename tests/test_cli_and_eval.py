"""CLI and evaluation tests (all through the public entry points)."""

import json

import jax
import numpy as np
import pytest

from shellac_tpu import get_model_config
from shellac_tpu.cli import main
from shellac_tpu.models import transformer
from shellac_tpu.training.data import token_batches, write_token_shard
from shellac_tpu.training.evaluate import evaluate


def _run(capsys, argv):
    rc = main(argv)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip())


class TestEvaluate:
    def test_perplexity_of_uniform_model(self):
        """A zero-logit model must score exactly log(V) nats/token."""
        cfg = get_model_config("tiny").replace(dtype="float32")
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        # Zero the output path: tied embeddings -> zero embed kills the
        # logits entirely (and the forward input too, but NLL of a
        # uniform softmax is log V regardless of the input).
        params["embed"] = params["embed"] * 0.0
        corpus = np.arange(2048, dtype=np.int32) % cfg.vocab_size
        out = evaluate(
            cfg, params,
            token_batches(corpus, batch_size=4, seq_len=32, num_batches=4),
        )
        assert out["loss"] == pytest.approx(np.log(cfg.vocab_size), rel=1e-4)
        assert out["tokens"] == 4 * 4 * 32

    def test_mask_weighting(self):
        cfg = get_model_config("tiny").replace(dtype="float32")
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        batch = {
            "inputs": np.ones((2, 16), np.int32),
            "targets": np.ones((2, 16), np.int32),
            "mask": np.concatenate(
                [np.ones((2, 8), np.float32), np.zeros((2, 8), np.float32)], 1
            ),
        }
        out = evaluate(cfg, params, iter([batch]))
        assert out["tokens"] == 16  # only unmasked positions count


class TestCLI:
    def test_info_lists_presets(self, capsys):
        out = _run(capsys, ["info"])
        assert "tiny" in out and "shellac-1b" in out

    def test_info_model(self, capsys):
        out = _run(capsys, ["info", "--model", "tiny"])
        assert out["params"] > 0
        assert out["config"]["d_model"] == 64

    def test_train_eval_generate_roundtrip(self, tmp_path, capsys):
        """Train on shards, checkpoint, eval the checkpoint, generate."""
        rng = np.random.default_rng(0)
        corpus = (np.arange(1 << 14) % 97).astype(np.int32)
        shard = tmp_path / "shard0.bin"
        write_token_shard(str(shard), corpus)
        ckpt = tmp_path / "ckpt"

        out = _run(capsys, [
            "train", "--model", "tiny", "--steps", "30",
            "--batch", "4", "--seq", "64",
            "--data", str(shard), "--ckpt-dir", str(ckpt),
            "--learning-rate", "3e-3",
        ])
        assert out["final_step"] == 30

        ev = _run(capsys, [
            "eval", "--model", "tiny", "--ckpt-dir", str(ckpt),
            "--data", str(shard), "--batches", "4",
            "--batch", "4", "--seq", "64",
        ])
        # 30 steps on a period-97 ramp: far below uniform log(256)=5.55.
        assert ev["loss"] < 5.0
        assert ev["tokens"] == 4 * 4 * 64

        gen = _run(capsys, [
            "generate", "--model", "tiny", "--ckpt-dir", str(ckpt),
            "--prompt", "1,2,3,4,5", "--max-new", "8",
            "--temperature", "0",
        ])
        assert len(gen["tokens"]) == 8

    def test_lora_finetune_roundtrip(self, tmp_path, capsys):
        """train --lora-rank over a frozen base, then eval/generate
        --lora-dir merge the adapters; adapters must actually help."""
        corpus = (np.arange(1 << 14) % 97).astype(np.int32)
        shard = tmp_path / "shard0.bin"
        write_token_shard(str(shard), corpus)
        base = tmp_path / "base"
        lora = tmp_path / "lora"

        # A briefly-trained base the adapters will specialize.
        _run(capsys, [
            "train", "--model", "tiny", "--steps", "10",
            "--batch", "4", "--seq", "64",
            "--data", str(shard), "--ckpt-dir", str(base),
            "--learning-rate", "3e-3",
        ])
        base_ev = _run(capsys, [
            "eval", "--model", "tiny", "--ckpt-dir", str(base),
            "--data", str(shard), "--batches", "4",
            "--batch", "4", "--seq", "64",
        ])

        out = _run(capsys, [
            "train", "--model", "tiny", "--steps", "40",
            "--batch", "4", "--seq", "64",
            "--data", str(shard),
            "--lora-rank", "4", "--lora-targets", "wq,wv,w_down",
            "--base-ckpt", str(base), "--ckpt-dir", str(lora),
            "--learning-rate", "1e-2",
        ])
        assert out["final_step"] == 40
        assert out["adapter_params"] > 0

        ev = _run(capsys, [
            "eval", "--model", "tiny", "--ckpt-dir", str(base),
            "--lora-dir", str(lora),
            "--data", str(shard), "--batches", "4",
            "--batch", "4", "--seq", "64",
        ])
        assert ev["loss"] < base_ev["loss"]

        gen = _run(capsys, [
            "generate", "--model", "tiny", "--ckpt-dir", str(base),
            "--lora-dir", str(lora),
            "--prompt", "1,2,3,4,5", "--max-new", "8",
            "--temperature", "0",
        ])
        assert len(gen["tokens"]) == 8

        # Adapters trained on a MESH must merge into a host-restored
        # base (sharded-save -> unsharded-merge crossed placements
        # before being pulled to host).
        lora_mesh = tmp_path / "lora_mesh"
        _run(capsys, [
            "train", "--model", "tiny", "--steps", "10",
            "--batch", "8", "--seq", "64", "--data", str(shard),
            "--lora-rank", "4", "--mesh", "fsdp=4,tp=2",
            "--base-ckpt", str(base), "--ckpt-dir", str(lora_mesh),
            "--learning-rate", "1e-2",
        ])
        gen = _run(capsys, [
            "generate", "--model", "tiny", "--ckpt-dir", str(base),
            "--lora-dir", str(lora_mesh),
            "--prompt", "1,2,3", "--max-new", "4", "--temperature", "0",
        ])
        assert len(gen["tokens"]) == 4

        # Resuming with mismatched flags must refuse rather than
        # clobber the adapter dir's metadata.
        with pytest.raises(SystemExit, match="adapters trained with"):
            main([
                "train", "--model", "tiny", "--steps", "50",
                "--batch", "4", "--seq", "64", "--data", str(shard),
                "--lora-rank", "4", "--base-ckpt", str(base),
                "--ckpt-dir", str(lora),  # default targets != original
            ])
        # And unsupported knobs are rejected loudly.
        with pytest.raises(SystemExit, match="grad-accum"):
            main([
                "train", "--model", "tiny", "--steps", "5",
                "--lora-rank", "4", "--grad-accum", "4",
            ])

    def test_generate_quantized(self, capsys):
        gen = _run(capsys, [
            "generate", "--model", "tiny", "--prompt", "1,2,3",
            "--max-new", "4", "--quantize", "--temperature", "0",
        ])
        assert len(gen["tokens"]) == 4

    def test_generate_speculative(self, capsys):
        gen = _run(capsys, [
            "generate", "--model", "tiny", "--prompt", "1,2,3",
            "--max-new", "6", "--draft-model", "tiny", "--gamma", "2",
            "--temperature", "0",
        ])
        assert len(gen["tokens"]) == 6
        assert 0.0 <= gen["accept_rate"] <= 1.0

    def test_config_json_override(self, tmp_path, capsys):
        cfg_file = tmp_path / "m.json"
        cfg_file.write_text(json.dumps({"preset": "tiny", "n_layers": 3}))
        out = _run(capsys, ["info", "--config", str(cfg_file)])
        assert out["config"]["n_layers"] == 3

    def test_train_with_mesh(self, tmp_path, capsys):
        out = _run(capsys, [
            "train", "--model", "tiny", "--steps", "3",
            "--batch", "8", "--seq", "32", "--mesh", "dp=4,tp=2",
        ])
        assert out["final_step"] == 3


def test_mesh_params_are_born_sharded(mesh8):
    """serve/batch --mesh: random-init params come out of
    _restore_params already in the mesh's shardings (no whole-model
    copy on the first device) and hold the same values as the
    unsharded init of the same seed (to the last ulp or so: one fused
    program against eager ops). The train final line and the
    serve start-up line name the device."""
    import argparse

    from shellac_tpu import cli
    from shellac_tpu.parallel.sharding import make_shardings
    from shellac_tpu.utils.metrics import device_info, device_memory

    cfg = get_model_config("tiny")
    args = argparse.Namespace(seed=3, ckpt_dir=None, ema=False)
    sharded = cli._restore_params(args, cfg, mesh=mesh8)
    plain = cli._restore_params(args, cfg)
    want = make_shardings(mesh8, transformer.logical_axes(cfg))
    for got, ref, sh in zip(jax.tree.leaves(sharded), jax.tree.leaves(plain),
                            jax.tree.leaves(want)):
        assert got.sharding.is_equivalent_to(sh, got.ndim)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-6, atol=1e-8)
    assert device_info() == {"platform": "cpu", "kind": "cpu", "count": 8}
    assert [m["id"] for m in device_memory()] == list(range(8))


def test_data_skip_resumes_stream():
    """skip=N must continue the same deterministic stream at batch N."""
    import numpy as np

    from shellac_tpu.training.data import token_batches

    corpus = np.arange(10_000, dtype=np.int32) % 251
    full = list(token_batches(
        corpus, batch_size=2, seq_len=32, seed=7, num_batches=6
    ))
    tail = list(token_batches(
        corpus, batch_size=2, seq_len=32, seed=7, num_batches=3, skip=3
    ))
    for a, b in zip(full[3:], tail):
        np.testing.assert_array_equal(a["inputs"], b["inputs"])
        np.testing.assert_array_equal(a["targets"], b["targets"])


def test_generate_cli_stop_sequences(capsys):
    """--stop truncates on both the plain and speculative paths."""
    import json

    from shellac_tpu.cli import main

    def run(argv):
        main(["generate", "--model", "tiny", "--prompt", "1,2,3",
              "--max-new", "6", "--seed", "0"] + argv)
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])

    full = run([])["tokens"]
    assert len(full) == 6
    # Stop on the first generated token: everything truncated.
    got = run(["--stop", str(full[0])])["tokens"]
    assert got == []
    # Stop on a 2-token sequence mid-output.
    got = run(["--stop", f"{full[2]},{full[3]}"])["tokens"]
    assert got == full[:2]
    # Speculative path honors the same flag.
    spec = run(["--draft-model", "tiny", "--gamma", "2",
                "--stop", str(full[0])])
    assert spec["tokens"] == [] or spec["tokens"][0] != full[0]

    import pytest

    with pytest.raises(SystemExit, match="bad token-id"):
        run(["--stop", "13,,10"])


def test_batch_cli(tmp_path, capsys):
    """Offline batch generation: JSONL in -> ordered JSONL out; row
    overrides (max_tokens, seed) apply; greedy rows match the Engine."""
    import json

    import jax
    import numpy as np

    from shellac_tpu import get_model_config
    from shellac_tpu.cli import main
    from shellac_tpu.inference.engine import Engine
    from shellac_tpu.models import transformer
    from shellac_tpu.training.tokenizer import ByteTokenizer

    inp = tmp_path / "in.jsonl"
    outp = tmp_path / "out.jsonl"
    rows = [
        {"prompt": "hello", "max_tokens": 6},
        {"prompt": [5, 9, 2], "max_tokens": 4, "seed": 7,
         "temperature": 0.9},
        {"prompt": "abc"},
    ]
    inp.write_text("\n".join(json.dumps(r) for r in rows))
    rc = main([
        "batch", "--model", "tiny", "--input", str(inp),
        "--output", str(outp), "--max-new", "5", "--slots", "2",
    ])
    assert rc == 0
    got = [json.loads(line) for line in outp.read_text().splitlines()]
    assert [g["index"] for g in got] == [0, 1, 2]
    assert [len(g["tokens"]) for g in got] == [6, 4, 5]
    # greedy row 0 equals the single-request Engine (same seed=0 init
    # the CLI uses for a random --model tiny)
    cfg = get_model_config("tiny").replace(dtype="float32")
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    ids = ByteTokenizer().encode("hello")
    ref = Engine(cfg, params, temperature=0.0).generate(
        np.asarray([ids], np.int32), max_new_tokens=6
    ).tokens[0]
    assert got[0]["tokens"] == list(np.asarray(ref))


def test_batch_cli_row_errors_and_scalar_stop(tmp_path):
    import json

    import pytest

    from shellac_tpu.cli import main

    inp = tmp_path / "in.jsonl"
    outp = tmp_path / "out.jsonl"
    # Scalar stop is ONE sequence (not per-character): stopping on "xyz"
    # can never trigger in 4 tokens of a 256-vocab byte model, so the
    # output keeps its full length (per-char stop on 'x'|'y'|'z' would
    # truncate with high probability over many tokens).
    inp.write_text(json.dumps(
        {"prompt": "hello", "max_tokens": 4, "stop": "xyz"}
    ))
    rc = main(["batch", "--model", "tiny", "--input", str(inp),
               "--output", str(outp)])
    assert rc == 0
    got = json.loads(outp.read_text())
    assert len(got["tokens"]) == 4

    # A malformed row names itself and exits cleanly before compute.
    inp.write_text(json.dumps({"prompt": ""}))
    with pytest.raises(SystemExit, match="row 0"):
        main(["batch", "--model", "tiny", "--input", str(inp),
              "--output", str(outp)])
