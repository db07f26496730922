"""The plain float32 reference against the program's model, at a tiny size
on the CPU, on the benchmark's own weights."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference as R
from bench import spec
from bench import weights as W
from bench.tests.conftest import TINY

SEED = 2**35 + 11


def _conf(dtype="float32", **kw):
    conf = spec.load_json(f"{spec.BENCH_DIR}/configs/yi-6b.json")
    conf.update(TINY, torch_dtype=dtype, **kw)
    return conf


def _ref_logits(conf, toks):
    h = jnp.stack([x[:toks.shape[1]] for x in R.hidden(conf, SEED, toks)])
    return np.asarray(R._mm(R._rms(h, W.global_f32(conf, SEED, "final_norm"),
                                   float(conf["rms_norm_eps"])),
                            W.global_f32(conf, SEED, "head")))


@pytest.mark.parametrize("kw", [{}, {"num_key_value_heads": 1},
                                {"num_hidden_layers": 3}])
def test_reference_matches_the_program_forward(kw):
    from repro.models import build_model
    conf = _conf(**kw)
    model = build_model(spec.model_config(conf))
    params = W.program_params(conf, SEED, model)
    toks = np.random.default_rng(0).integers(1, 256, (2, 40)).astype(np.int32)
    got = np.asarray(jax.jit(model.forward)(params, {"tokens":
                                                     jnp.asarray(toks)})[0])
    ref = _ref_logits(conf, toks)
    # float32 both sides
    assert np.abs(got - ref).max() < 2e-4 * np.abs(ref).max()


def test_layer_weights_equal_the_program_stack():
    from repro.models import build_model
    conf = _conf(dtype="bfloat16")
    params = W.program_params(conf, SEED, build_model(spec.model_config(conf)))
    w1 = W.layer_f32(conf, SEED, 1)
    b0 = params["stack"]["b0"]
    for ours, theirs in [("wq", b0["mixer"]["wq"]), ("w_up", b0["ffn"]["wi"]),
                         ("w_gate", b0["ffn"]["wg"]),
                         ("attn_norm", b0["norm1"]["scale"])]:
        np.testing.assert_array_equal(np.asarray(w1[ours]),
                                      np.asarray(theirs[1], np.float32))


def test_gap_is_zero_for_the_references_own_greedy_tokens():
    conf = _conf()
    prompt = np.arange(1, 21, dtype=np.int32)
    seq = list(prompt)
    for _ in range(6):
        seq.append(int(np.argmax(_ref_logits(conf, np.asarray([seq]))[0, -1])))
    out = np.asarray(seq[20:], np.int32)
    g = R.gaps(conf, SEED, [(prompt, out)])
    assert g["tokens"] == 6 and g["widest_gap"] == pytest.approx(0.0, abs=1e-5)
    bad = out.copy()
    bad[3] = (bad[3] + 1) % 256
    assert R.gaps(conf, SEED, [(prompt, bad)])["widest_gap"] > 0.01


def test_published_epsilon_moves_logits_by_rounding_only():
    """The configuration states the program's 1e-6 in place of Yi's
    published 1e-5 (``reduced``); on the benchmark's weights the
    published value moves the reference's logits far below any limit."""
    conf = _conf()
    toks = np.random.default_rng(1).integers(1, 256, (2, 40)).astype(np.int32)
    ran = _ref_logits(conf, toks)
    pub = _ref_logits(dict(conf, rms_norm_eps=conf["published"]["rms_norm_eps"]),
                      toks)
    assert np.abs(ran - pub).max() < 1e-4 * np.abs(ran).max()
