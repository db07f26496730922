"""Each architecture's plain float32 reference against the program's
model, at the module's tiny size on the CPU, on the benchmark's own
weights."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference as R
from bench import spec

SEED = 2**35 + 11
CONFIGS = sorted(os.path.basename(p)[:-5] for p in glob.glob(
    os.path.join(spec.BENCH_DIR, "configs", "*.json")))
# Llama variants of the tiny size: one kv head (GQA group 4), three layers
LLAMA_VARIANTS = [{"num_key_value_heads": 1}, {"num_hidden_layers": 3}]


def _arch(config="yi-6b", dtype="float32", **kw):
    conf = spec.load_json(f"{spec.BENCH_DIR}/configs/{config}.json")
    conf = spec.architecture(dict(conf, torch_dtype=dtype)).tiny()
    return spec.architecture(dict(conf, **kw))


def _ref_logits(arch, toks):
    h = jnp.stack([x[:toks.shape[1]] for x in arch.hidden(SEED, toks)])
    norm, head, eps = arch.final(SEED)
    return np.asarray(R.mm(R.rms(h, norm, eps), head))


@pytest.mark.parametrize("config,kw", [(c, {}) for c in CONFIGS]
                         + [("yi-6b", kw) for kw in LLAMA_VARIANTS])
def test_reference_matches_the_program_forward(config, kw):
    from repro.models import build_model
    arch = _arch(config, **kw)
    model = build_model(arch.program_config())
    params = arch.program_params(SEED, model)
    vocab = arch.conf["vocab_size"]
    toks = np.random.default_rng(0).integers(1, vocab, (2, 40)).astype(
        np.int32)
    got = np.asarray(jax.jit(model.forward)(params, {"tokens":
                                                     jnp.asarray(toks)})[0])
    ref = _ref_logits(arch, toks)
    # float32 both sides
    assert np.abs(got - ref).max() < 2e-4 * np.abs(ref).max()


def test_layer_weights_equal_the_program_stack():
    from repro.models import build_model
    arch = _arch(dtype="bfloat16")
    params = arch.program_params(SEED, build_model(arch.program_config()))
    w1 = arch.module.layer_f32(arch.conf, SEED, 1)
    b0 = params["stack"]["b0"]
    for ours, theirs in [("wq", b0["mixer"]["wq"]), ("w_up", b0["ffn"]["wi"]),
                         ("w_gate", b0["ffn"]["wg"]),
                         ("attn_norm", b0["norm1"]["scale"])]:
        np.testing.assert_array_equal(np.asarray(w1[ours]),
                                      np.asarray(theirs[1], np.float32))


def test_gap_is_zero_for_the_references_own_greedy_tokens():
    arch = _arch()
    prompt = np.arange(1, 21, dtype=np.int32)
    seq = list(prompt)
    for _ in range(6):
        seq.append(int(np.argmax(_ref_logits(arch, np.asarray([seq]))[0, -1])))
    out = np.asarray(seq[20:], np.int32)
    g = R.gaps(arch, SEED, [(prompt, out)])
    assert g["tokens"] == 6 and g["widest_gap"] == pytest.approx(0.0, abs=1e-5)
    bad = out.copy()
    bad[3] = (bad[3] + 1) % 256
    assert R.gaps(arch, SEED, [(prompt, bad)])["widest_gap"] > 0.01


def test_published_epsilon_moves_logits_by_rounding_only():
    """The configuration states the program's 1e-6 in place of Yi's
    published 1e-5 (``reduced``); on the benchmark's weights the
    published value moves the reference's logits far below any limit."""
    arch = _arch()
    toks = np.random.default_rng(1).integers(1, 256, (2, 40)).astype(np.int32)
    ran = _ref_logits(arch, toks)
    pub = _ref_logits(spec.architecture(dict(
        arch.conf, rms_norm_eps=arch.conf["published"]["rms_norm_eps"])), toks)
    assert np.abs(ran - pub).max() < 1e-4 * np.abs(ran).max()
