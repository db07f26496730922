"""Architecture modules (``bench/arch/<model_type>.py``): the contract each
configuration and module keeps, and the Llama module's counts, weights,
reference gap and readers held to the values the harness gave before
they were moved behind it (computed on the CPU, as hard-coded here)."""
import glob
import hashlib
import os

import jax
import numpy as np
import pytest

from bench import peaks as P
from bench import reference as R
from bench import spec
from bench.driver import TickLog
from bench.run import Run

CONFIGS = sorted(os.path.basename(p)[:-5] for p in glob.glob(
    os.path.join(spec.BENCH_DIR, "configs", "*.json")))
MODULES = spec.architectures()
SEED = 2**35 + 11


def _conf(config):
    return spec.load_json(f"{spec.BENCH_DIR}/configs/{config}.json")


# -- the contract, for every configuration and every module -----------------

@pytest.mark.parametrize("config", CONFIGS)
def test_every_configuration_resolves_to_its_module(config):
    conf = _conf(config)
    arch = spec.architecture(conf)
    assert conf["model_type"] in MODULES
    assert arch.module.__file__ == os.path.join(
        spec.ARCH_DIR, conf["model_type"] + ".py")
    assert arch.conf is conf


@pytest.mark.parametrize("model_type", [None, "no_such_architecture",
                                        "../metrics/decode_mfu"])
def test_an_unknown_model_type_names_the_known_modules(model_type):
    conf = dict(_conf(CONFIGS[0]), model_type=model_type)
    if model_type is None:
        del conf["model_type"]
    with pytest.raises(SystemExit) as e:
        spec.architecture(conf)
    assert all(m in str(e.value) for m in MODULES)


@pytest.mark.parametrize("module", MODULES)
def test_each_module_exports_the_interface_and_serves_a_configuration(module):
    served = [c for c in CONFIGS if _conf(c)["model_type"] == module]
    assert served, f"no configuration names model_type {module!r}"
    arch = spec.architecture(_conf(served[0]))
    for name in spec.INTERFACE:
        assert callable(getattr(arch.module, name)), name
        assert callable(getattr(arch, name)), name


@pytest.mark.parametrize("config", CONFIGS)
def test_program_params_pass_the_layout_check(config):
    from repro.models import build_model
    arch = spec.architecture(spec.architecture(_conf(config)).tiny())
    model = build_model(arch.program_config())
    params = arch.program_params(SEED, model)
    assert jax.tree.structure(params) == jax.tree.structure(
        jax.eval_shape(model.init, jax.random.key(0)))
    # the check stops a layout that is not the model's
    wider = spec.architecture(dict(arch.conf, vocab_size=2
                                   * arch.conf["vocab_size"]))
    with pytest.raises(SystemExit, match="layout changed"):
        wider.program_params(SEED, model)


# -- Llama parity with the values before the move ---------------------------

POS = [0, 1, 127, 600, 2047]
PRE = [(1, 0), (37, 1000), (128, 512), (600, 0), (1536, 0)]
POSSETS = [[0], [5, 900], [15, 127, 128, 2046]]
PARENT = {
    "yi-6b": {
        "layers": 32,
        "decode": [11597774848, 11598299136, 11664359424, 11912347648,
                   12670992384],
        "prefill": [11597774848, 429991133184, 1456551755776,
                    6738830950400, 17627472723968],
        "fused": [(16384, 20480), (14860288, 1894400), (38010880, 4825088)],
        "pattn": [(16384, 18432), (617725952, 2729984), (1209008128, 3407872),
                  (2954035200, 11059200), (19339935744, 28311552)],
        "readers": {"decode_mfu": 2.5580109103214888,
                    "prefill_mfu": 72.97270591675127,
                    "step_mfu": 11.080201460304568,
                    "fused_decode_roofline": 14.233474969474969,
                    "prefill_attn_roofline": 18.490704530434815},
    },
    "yi-34b-8l": {
        "layers": 8,
        "decode": [9843212288, 9843441664, 9872343040, 9980837888,
                   10312744960],
        "prefill": [9843212288, 339808387072, 1160304918528, 5397561344000,
                    13981212213248],
        "fused": [(28672, 36864), (26005504, 3780608), (66519040, 9633792)],
        "pattn": [(28672, 32768), (1081020416, 5308416),
                  (2115764224, 6291456), (5169561600, 19660800),
                  (33844887552, 50331648)],
        "readers": {"decode_mfu": 2.156661127580372,
                    "prefill_mfu": 58.35596150253807,
                    "step_mfu": 8.86661893457868,
                    "fused_decode_roofline": 7.0942319902319895,
                    "prefill_attn_roofline": 8.212174166837698},
    },
}
# sha256 over each leaf's path, dtype, shape and bytes, in tree order
PARAMS_DIGEST = {
    "float32": "cfd83c2d20a27283a7030c3b02a4206e856f87f3677b88b0ce9c2f17cf6fe438",
    "bfloat16": "66d57c95717b7b5eb1ebef96b709bdeb5b3406005ddab48a45af8c7bc95ba122",
}


@pytest.mark.parametrize("config", sorted(PARENT))
def test_llama_counts_equal_the_parents(config):
    arch, want = spec.architecture(_conf(config)), PARENT[config]
    assert [arch.decode_token_flops(p) for p in POS] == want["decode"]
    assert [arch.prefill_flops(n, o) for n, o in PRE] == want["prefill"]
    assert [arch.fused_decode_calls(ps) for ps in POSSETS] == [
        [(f, b, want["layers"])] for f, b in want["fused"]]
    assert [arch.prefill_attn_calls(n, o) for n, o in PRE] == [
        [(f, b, want["layers"])] for f, b in want["pattn"]]


def _digest(params):
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("dtype", sorted(PARAMS_DIGEST))
def test_llama_program_params_equal_the_parents(dtype):
    from repro.models import build_model
    arch = spec.architecture(spec.architecture(
        dict(_conf("yi-6b"), torch_dtype=dtype)).tiny())
    params = arch.program_params(SEED, build_model(arch.program_config()))
    assert _digest(params) == PARAMS_DIGEST[dtype]


def test_llama_gaps_equal_the_parents():
    arch = spec.architecture(spec.architecture(
        dict(_conf("yi-6b"), torch_dtype="float32")).tiny())
    rng = np.random.default_rng(5)
    rows = [(rng.integers(1, 256, n).astype(np.int32),
             rng.integers(1, 256, m).astype(np.int32))
            for n, m in ((40, 9), (530, 12))]
    g = R.gaps(arch, SEED, rows, control=True)
    assert g == {"widest_gap": 4.348625183105469, "tokens": 21, "rows": 2,
                 "control_gap": 0.031723976135253906}


US = 1000                    # ns


def _fixed_run(config):
    """A hand-made tick log and trace: four ticks, decode and prefill
    modules and both kernels in a 400 ms window."""
    ticks = [TickLog([5, 900, 17], [(600, 0)]), TickLog([6, 901, 18, 0], []),
             TickLog([], [(37, 1000), (128, 512)]), TickLog([7, 902], [])]
    trace = {"host": [["bench.window", 0, 400_000 * US]],
             "devices": {"/device:TPU:0": {
                 "ops": [["%fused_paged_decode_grouped.3 = bf16[1] "
                          "custom-call()", 1000 * US, 1700 * US],
                         ["fused_paged_decode_grouped.4", 20_000 * US,
                          20_900 * US],
                         ["paged_prefill_attention_grouped.6", 30_000 * US,
                          34_234 * US],
                         ["fusion", 40_000 * US, 60_000 * US]],
                 "modules": [["jit_serve_step(123)", 1000 * US, 13_000 * US],
                             ["jit_serve_step(123)", 20_000 * US, 29_000 * US],
                             ["jit_prefill_suffix_paged(9)", 30_000 * US,
                              90_000 * US]]}}}
    return Run(trace=trace, ticks=ticks,
               arch=spec.architecture(_conf(config)),
               peak=P.peak("TPU v5 lite"))


@pytest.mark.parametrize("config", sorted(PARENT))
def test_llama_readers_equal_the_parents(config):
    run = _fixed_run(config)
    got = {m: spec.reader(m)(run) for m in PARENT[config]["readers"]}
    assert got == PARENT[config]["readers"]
