"""Driver CLIs run end-to-end on the virtual CPU mesh (the reference's
runnable-tutorial-as-integration-test pattern, SURVEY §4)."""

import pytest

from pipe_tpu.apps import lm_tutorial, zoo


def test_lm_tutorial_tiny(capsys):
    rc = lm_tutorial.main(["except_last", "--tiny", "--steps", "3",
                           "--schedule", "1f1b"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "loss" in out and "val loss" in out


@pytest.mark.parametrize("family,schedule", [
    ("gpt2", "1f1b"),
    ("bert", "interleaved-1f1b"),
    ("vit", "gpipe"),
])
def test_zoo_families(family, schedule, capsys):
    rc = zoo.main([family, "--tiny", "--steps", "2",
                   "--schedule", schedule])
    assert rc == 0
    out = capsys.readouterr().out
    assert "final loss" in out


def test_generate_cli_single_and_pipelined(capsys):
    from pipe_tpu.apps import generate

    rc = generate.main(["--tiny", "--max-new", "5", "--prompt", "3,4,5"])
    assert rc == 0
    single = capsys.readouterr().out.strip().splitlines()
    assert len(single) == 1 and len(single[0].split(",")) == 5

    rc = generate.main(["--tiny", "--stages", "2", "--max-new", "5",
                        "--prompt", "3,4,5"])
    assert rc == 0
    piped = capsys.readouterr().out.strip().splitlines()
    assert len(piped) == 2
    # greedy: pipelined rows match the single-device row token-for-token
    assert piped[0] == piped[1] == single[0]


def test_generate_cli_rejects_bad_prompt(capsys):
    from pipe_tpu.apps import generate

    assert generate.main(["--tiny", "--prompt", "999999"]) == 2


def test_generate_cli_resume_roundtrip(tmp_path, capsys):
    """Train -> save -> serve the checkpoint at a DIFFERENT stage count;
    restored weights (not fresh init) must drive the sample."""
    import numpy as np

    from pipe_tpu.apps import generate
    from pipe_tpu.data import lm_text
    from pipe_tpu.models.transformer_lm import LMConfig
    from pipe_tpu.train.loop import Trainer, TrainerConfig
    from pipe_tpu.train.state import save_checkpoint

    model = LMConfig().tiny()
    cfg = TrainerConfig(batch_size=8, bptt=16, chunks=2, n_stages=2,
                        lr=0.05, schedule="gpipe", checkpoint="never")
    ids = np.random.default_rng(11).integers(
        0, model.vocab, size=2048).astype(np.int32)
    src = lm_text.batchify(ids, cfg.batch_size)
    tr = Trainer(model, cfg)
    state, _ = tr.train_epoch(src, state=tr.init_state(), max_steps=2,
                              log_every=0)
    ckpt = str(tmp_path / "ck")
    save_checkpoint(ckpt, state, 1)

    args = ["--tiny", "--max-new", "6", "--prompt", "3,4,5"]
    assert generate.main(args + ["--resume", ckpt]) == 0
    restored = capsys.readouterr().out.strip().splitlines()
    # 2-stage checkpoint served pipelined on 4 stages: same tokens
    assert generate.main(args + ["--resume", ckpt, "--stages", "4"]) == 0
    re4 = capsys.readouterr().out.strip().splitlines()
    assert len(re4) == 4 and all(r == restored[0] for r in re4)
    # fresh init differs (proves the restore took)
    assert generate.main(args) == 0
    fresh = capsys.readouterr().out.strip().splitlines()
    assert fresh[0] != restored[0]


def test_generate_cli_resume_interleaved_layout(tmp_path, capsys):
    """Interleaved training stacks virtual stages device-major-permuted;
    the layout record must make serving reconstruct the TRUE layer order
    (without it, layers [0,2,1,3] would silently serve as [0,1,2,3])."""
    import jax
    import numpy as np

    from pipe_tpu.apps import generate
    from pipe_tpu.data import lm_text
    from pipe_tpu.inference import GenerationConfig, Generator
    from pipe_tpu.models.transformer_lm import LMConfig, PipelinedLM
    from pipe_tpu.train.loop import Trainer, TrainerConfig

    model = LMConfig().tiny()  # 4 layers = 2 stages x interleave 2
    cfg = TrainerConfig(batch_size=8, bptt=16, chunks=2, n_stages=2,
                        interleave=2, lr=0.05, schedule="interleaved-1f1b",
                        checkpoint="never")
    ids = np.random.default_rng(13).integers(
        0, model.vocab, size=2048).astype(np.int32)
    src = lm_text.batchify(ids, cfg.batch_size)
    tr = Trainer(model, cfg)
    state, _ = tr.train_epoch(src, state=tr.init_state(), max_steps=1,
                              log_every=0)
    ckpt = str(tmp_path / "ck")
    tr.save(ckpt, state)

    assert generate.main(["--tiny", "--resume", ckpt, "--max-new", "6",
                          "--prompt", "3,4,5"]) == 0
    served = capsys.readouterr().out.strip().splitlines()[0]

    # ground truth: un-permute the trained stacked params by hand and run
    # the single-device generator over them in true layer order
    ssp = jax.tree_util.tree_map(np.asarray, state.params[0])
    d, v = 2, 2
    flat = []
    for vs in range(4):
        row = (vs % d) * v + vs // d
        flat.append(jax.tree_util.tree_map(lambda a: a[row], ssp[0]))
    m1 = PipelinedLM(model, 1)
    pre = jax.tree_util.tree_map(np.asarray, state.params[1])
    post = jax.tree_util.tree_map(np.asarray, state.params[2])
    ref = Generator(m1, GenerationConfig(max_new_tokens=6,
                                         temperature=0.0)).generate(
        ([flat], pre, post), np.asarray([[3, 4, 5]], dtype=np.int32))
    ref_row = ",".join(str(int(t)) for t in np.asarray(ref)[0])
    assert served == ref_row


def test_generator_position_table_guard():
    import jax.numpy as jnp
    import pytest as pt

    from pipe_tpu.inference import GenerationConfig, Generator
    from pipe_tpu.models.transformer_lm import LMConfig, PipelinedLM

    model = PipelinedLM(LMConfig().tiny(), 1)
    params = None  # never reached
    g = Generator(model, GenerationConfig(max_new_tokens=10_000))
    with pt.raises(ValueError, match="positional table"):
        g.generate(params, jnp.zeros((1, 4), jnp.int32))


def test_generate_cli_gpt2_family(capsys):
    from pipe_tpu.apps import generate

    args = ["--tiny", "--family", "gpt2", "--max-new", "5",
            "--prompt", "3,4,5"]
    assert generate.main(args) == 0
    single = capsys.readouterr().out.strip().splitlines()
    assert len(single) == 1 and len(single[0].split(",")) == 5
    assert generate.main(args + ["--stages", "2"]) == 0
    piped = capsys.readouterr().out.strip().splitlines()
    assert piped == [single[0], single[0]]


def test_generate_cli_context_shards(capsys):
    from pipe_tpu.apps import generate

    base = ["--tiny", "--max-new", "5", "--prompt", "3,4,5,6,1,2,3,4"]
    assert generate.main(base) == 0
    single = capsys.readouterr().out.strip().splitlines()
    assert generate.main(base + ["--context-shards", "4"]) == 0
    ctx = capsys.readouterr().out.strip().splitlines()
    assert ctx == single  # sharded prompt cache, same tokens
    # indivisible prompt rejected cleanly
    assert generate.main(["--tiny", "--prompt", "1,2,3",
                          "--context-shards", "4"]) == 2


def test_serve_cli_exit_code_reports_engine_errors(monkeypatch, capsys):
    """The engine contains a backend failure to the requests it hit; the
    driver's exit code still says a request ended in an engine error."""
    import functools
    import json

    import pipe_tpu.serve as serve_pkg
    from pipe_tpu.apps import serve
    from pipe_tpu.resilience import ChaosPlan, Fault

    # every prefill admitted in tick 0 raises inside the engine
    monkeypatch.setattr(serve_pkg, "ServeEngine", functools.partial(
        serve_pkg.ServeEngine,
        chaos=ChaosPlan([Fault("backend_raise", step=0)])))
    rc = serve.main(["--tiny", "--requests", "2", "--max-new", "2",
                     "--slots", "2"])
    out, err = capsys.readouterr()
    assert rc == 1
    lines = [json.loads(ln) for ln in out.strip().splitlines()]
    assert [ln["status"] for ln in lines[:-1]] == ["error", "error"]
    assert lines[-1]["summary"]["errors"] == 2
    assert "2 request(s) ended in an engine error" in err
    assert "ChaosError" in err
