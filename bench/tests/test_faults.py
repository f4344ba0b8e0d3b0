"""A run with the timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip and drives the rest of a run
of a cell at the program's reduced size on the CPU: the engine or the
gang step is broken after it is built, the window runs on it, and the
comparison with the plain reference has to fail.
"""
import jax
import jax.numpy as jnp
import numpy as np

import tiny


def test_sound_serving_run_is_correct():
    res = tiny.run_serve(tiny.serve_spec("chat"), 2**31 + 3, 2.0)
    assert res["correct"], res["checks"]


def test_sound_closed_loop_serving_run_is_correct():
    res = tiny.run_serve(tiny.serve_spec("docs"), 2**31 + 4, 2.0)
    assert res["correct"], res["checks"]
    assert res["metrics"]["setup_s"]["value"] > 0


def test_serving_token_altered_where_produced():
    def alter(engine):
        real = engine.decode_step
        vocab = engine.cfg.vocab_size

        def decode(params, cache, batch):
            cache, tok, lm = real(params, cache, batch)
            return cache, (tok + 1) % vocab, lm
        engine.decode_step = decode

    res = tiny.run_serve(tiny.serve_spec("chat"), 2**31 + 3, 2.0,
                         break_engine=alter)
    assert not res["correct"]
    assert not res["checks"]["logit_gap"]["ok"]


def _patch_train_step(wrap):
    import train_driver
    build = train_driver.build

    def broken(*a, **k):
        gang = build(*a, **k)
        gang["step_fn"] = wrap(gang["step_fn"])
        return gang
    train_driver.build = broken

    def mend():
        train_driver.build = build
    return mend


def test_sound_training_run_is_correct():
    res = tiny.run_cell(tiny.train_spec(), 2**31 + 5, 1.0)
    assert res["correct"], res["checks"]


def test_training_step_returns_its_state_unchanged():
    def wrap(step):
        def same(p, o, batch, hp, t):
            cp = jax.tree.map(jnp.copy, (p, o))
            _, _, m = step(cp[0], cp[1], batch, hp, t)
            return p, o, m
        return same

    res = tiny.run_cell(tiny.train_spec(), 2**31 + 5, 1.0,
                        patch=lambda: _patch_train_step(wrap))
    assert not res["correct"]
    assert not res["checks"]["change_gap"]["ok"]


def test_training_half_the_batch_left_out():
    def wrap(step):
        def half(p, o, batch, hp, t):
            b = dict(batch)
            for k in ("tokens", "labels"):
                a = np.array(b[k])
                m = a.shape[1]
                a[:, m // 2:] = a[:, :m - m // 2]
                b[k] = a
            return step(p, o, b, hp, t)
        return half

    res = tiny.run_cell(tiny.train_spec(), 2**31 + 5, 1.0,
                        patch=lambda: _patch_train_step(wrap))
    assert not res["correct"]
