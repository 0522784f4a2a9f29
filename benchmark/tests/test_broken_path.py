"""A whole run with the timed path broken underneath comes out as not
correct.  The rehearsal's sizes on the CPU (which is how a run skips the
look for a chip); everything after that is the run a chip would make:
the same driver, window, check and result line."""
import io
import json
import os
import sys
from contextlib import redirect_stdout

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402


def last_line(workload, hooks=None, seconds="1.5"):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = bench_run.main(["--rehearse", "--workload", workload, "--seed",
                             "2147483659", "--seconds", seconds], hooks)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1]), \
        out.getvalue()


def frozen_step(trainer, batch):
    """A step that returns its state unchanged: the loss is computed,
    the update is thrown away."""
    import jax
    import jax.numpy as jnp
    before = jax.tree_util.tree_map(jnp.copy,
                                    (trainer.params, trainer.opt_state))
    loss = float(trainer.train_step(batch[0], batch[1]))
    trainer.params, trainer.opt_state = before
    return loss


def half_batch_step(trainer, batch):
    """A step that leaves out a part of the batch: the second half of the
    rows is the first half again."""
    import numpy as np
    ids, labels = batch
    half = ids.shape[0] // 2
    ids = np.concatenate([ids[:half], ids[:half]])
    labels = np.concatenate([labels[:half], labels[:half]])
    return float(trainer.train_step(ids, labels))


def off_by_one_engine(config, flat):
    """An engine whose decode step hands back every token plus one."""
    from benchmark.drivers import serve
    engine = serve.build_engine(config, flat)
    real = engine._decode_jit
    vocab = config["model"]["kwargs"]["vocab_size"]

    def broken(*args, **kwargs):
        nxt, *rest = real(*args, **kwargs)
        return ((nxt + 1) % vocab, *rest)
    engine._decode_jit = broken
    return engine


def test_sound_train_run_is_correct():
    line, _ = last_line("train_350m_seq2048")
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["metrics"] == {}


@pytest.mark.parametrize("step_fn", [frozen_step, half_batch_step])
def test_broken_train_step_is_not_correct(step_fn):
    line, out = last_line("train_350m_seq2048", {"step_fn": step_fn})
    assert line["correct"] is False, out


def test_sound_serve_run_is_correct():
    line, _ = last_line("serve_1.3b_closed")
    assert line["correct"] is True and line["failed"] == 0


def test_altered_tokens_are_not_correct():
    line, out = last_line("serve_1.3b_closed",
                          {"build_engine": off_by_one_engine})
    assert line["correct"] is False, out
    assert '"served_logit_deficit_max"' in out


def test_open_loop_mix_drives_a_whole_run():
    """No cell uses an open loop yet (PERF.md section 7); the generator
    and the drive loop carry one all the same, so that a later PR can add
    such a cell as data."""
    from benchmark import harness, trafficgen
    from benchmark.drivers import serve
    parts = harness.load_cell(harness.load_spec(), "serve_1.3b_closed",
                              rehearse=True)
    jax, devices = harness.start_jax(1, rehearse=True)
    result = serve.run({
        "jax": jax, "devices": devices, "workload": "open_loop_test",
        "seed": 5, "seconds": 2.0, "trace": False, "peaks": None,
        "config": parts["config"], "t_process_start": 0.0,
        "mix": trafficgen.load_mix("chat_poisson", rehearse=True)})
    assert result["check"].correct
    assert result["attempted"] >= 4 and result["failed"] == 0
    assert len(result["obs"]["window"]["late_ms"]) == result["attempted"]
