"""CPU tier of the chip check (chip_smoke.py; ISSUE 21).

The chip itself is only reached by ``python chip_smoke.py`` on a machine
that has one.  Here: the script refuses a CPU, its legs run at toy size with
interpret-mode kernels, the compile cache lands where the environment says,
and the multi-chip dry run refuses to invent devices.
"""
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _run(argv, cwd=REPO, **env):
    full = {k: v for k, v in os.environ.items()
            if k not in ("JAX_COMPILATION_CACHE_DIR", "MXNET_AOT_CACHE")}
    full.update(env)
    return subprocess.run([sys.executable] + argv, cwd=cwd, env=full,
                          capture_output=True, text=True, timeout=300)


# -- (a) no chip, no result --------------------------------------------------
def test_refuses_cpu_and_names_what_it_found():
    res = _run(["chip_smoke.py"], JAX_PLATFORMS="cpu")
    assert res.returncode != 0
    assert "platform='cpu'" in res.stderr and "needs a TPU" in res.stderr
    assert '"ok"' not in res.stdout


def test_fails_alone_in_a_directory(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    res = _run(["chip_smoke.py"], cwd=str(tmp_path), JAX_PLATFORMS="cpu")
    assert res.returncode != 0 and '"ok"' not in res.stdout
    assert "mxnet_tpu" in res.stderr


# -- (b) the legs, toy size, interpret-mode kernels --------------------------
def test_quant_leg_interpret():
    assert chip_smoke.quant_leg(shape=(32, 128), interpret=True) == {
        "quantize_mismatches": 0, "dequantize_max_err": 0.0}


def test_nms_leg_interpret():
    facts = chip_smoke.nms_leg(boxes=300, batch=2, interpret=True)
    assert facts["nms_mismatches"] == 0
    assert all(0 < n < 300 for n in facts["nms_survivors"])


def test_dconv_leg_interpret():
    facts = chip_smoke.dconv_leg(bg=2, channels=8, hw=(12, 16),
                                 interpret=True, calls=1)
    assert facts["bg"] == 2
    regimes = facts["dconv"]
    assert list(regimes) == [name for name, _ in chip_smoke.DCONV_REGIMES]
    for fact in regimes.values():
        assert set(fact["rel_err"]) == {"col", "d_ly", "d_lx", "d_lf", "d_ft"}
        assert fact["fwd_ms"] > 0 and fact["bwd_ms"] > 0
    # narrow where a detector's offsets are small, the whole map otherwise
    assert regimes["offsets<1"]["band_share"] <= regimes["offsets<=3"][
        "band_share"] <= regimes["uniform"]["band_share"] == 1.0


def test_sparse_attn_leg_interpret():
    # two spans of 256 keys, blocks of 32 queries, one key-value head of 8
    facts = chip_smoke.sparse_attn_leg(
        seq=512, heads=8, kv_heads=1, index_heads=4, index_dim=16, topk=96,
        block=32, span=256, interpret=True, calls=1)["sparse_attn"]
    assert facts["seq"] == 512
    assert facts["kernel"]["selected"] == facts["walk"]["selected"] > 0
    assert set(facts["rel_err"]) == {"o", "kl", "d_q", "d_k", "d_v", "d_iq",
                                     "d_ik", "d_iw"}
    assert max(facts["rel_err"].values()) <= 2.0 ** -5
    for path in ("kernel", "walk"):
        assert facts[path]["fwd_ms"] > 0 and facts[path]["fwd_bwd_ms"] > 0


def test_psroi_leg_toy():
    # 2 x 40 rois, 6 channels a class: the classes pooling is over the
    # one-hot threshold, so the leg's check compares the two paths
    facts = chip_smoke.psroi_leg(batch=2, rois=40, hw=(12, 16), k=3, calls=1,
                                 poolings=(("offsets", 2, True),
                                           ("classes", 6, False)))
    assert facts["rois"] == 80 and list(facts["psroi"]) == ["offsets",
                                                            "classes"]
    for fact in facts["psroi"].values():
        assert fact["rel_err"] <= 2.0 ** -6
        assert fact["fwd_ms"] > 0 and fact["fwd_bwd_ms"] > 0


def test_module_fit_leg_toy():
    facts = chip_smoke.module_fit_leg(num_layers=8, image=16, classes=10,
                                      batch=4, batches=3)
    assert facts["fused"] and facts["param_platforms"] == ["cpu"]
    assert len(facts["running_cross_entropy"]) == 3


@pytest.mark.slow  # ~25 s of XLA:CPU compile; ci/run_tests.sh unit runs it
def test_rfcn_leg_toy():
    facts = chip_smoke.rfcn_leg(resnet101=False, batch=1, steps=3,
                                dtype=None)
    assert len(set(facts["losses"])) == 3
    # a CPU lowering holds no Mosaic call, and the check says so
    assert facts["mosaic_calls"] == []
    with pytest.raises(AssertionError, match="lacks Mosaic calls"):
        chip_smoke.check_step_kernels(facts)


def test_mosaic_calls_reads_compiled_text():
    dconv = (
        '  %cc.N = bf16[32,21888,128]{2,1,0:T(8,128)(2,1)} custom-call('
        '%a, %b), custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(step)/jvp(dconv_col_pallas_fwd)/pallas_call" '
        'source_line=1}')
    hlo = "\n".join([
        dconv.replace("N", "1"), dconv.replace("N", "2"),
        '  %cc.3 = (f32[8,1,6144]{2,1,0}, f32[8]{0}) custom-call('
        '%c), custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(step)/nms_alive_pallas/pallas_call"}',
        '  %cc.4 = f32[4]{0} custom-call(f32[4]{0} %c),'
        ' custom_call_target="Sharding"',
    ])
    assert chip_smoke.mosaic_calls(hlo) == [
        {"kernel": "dconv_col_pallas_fwd", "count": 2,
         "result": "bf16[32,21888,128]"},
        {"kernel": "nms_alive_pallas", "count": 1,
         "result": "(f32[8,1,6144], f32[8])"}]


# -- (c) cache placement ------------------------------------------------------
_PRINT_DIR = ("import mxnet_tpu, jax; "
              "print('DIR=%s' % jax.config.jax_compilation_cache_dir)")


@pytest.mark.parametrize("env,want", [
    # set from outside: nothing set in code, with or without MXNET_AOT_CACHE
    ({"JAX_COMPILATION_CACHE_DIR": "{tmp}/outside", "JAX_PLATFORMS": "tpu"},
     "{tmp}/outside"),
    ({"JAX_COMPILATION_CACHE_DIR": "{tmp}/outside", "JAX_PLATFORMS": "tpu",
      "MXNET_AOT_CACHE": "{tmp}/aot"}, "{tmp}/outside"),
    ({"JAX_COMPILATION_CACHE_DIR": "{tmp}/outside", "JAX_PLATFORMS": "cpu",
      "MXNET_AOT_CACHE": "{tmp}/aot"}, "{tmp}/outside"),
    # unset, accelerator hinted: the fixed directory beside the package
    ({"JAX_PLATFORMS": "tpu", "MXNET_AOT_CACHE": "{tmp}/aot"},
     os.path.join(REPO, ".jax_cache")),
    # unset on CPU: no persistent cache
    ({"JAX_PLATFORMS": "cpu", "MXNET_AOT_CACHE": "{tmp}/aot"}, "None"),
])
def test_cache_placement(tmp_path, env, want):
    env = {k: v.format(tmp=tmp_path) for k, v in env.items()}
    res = _run(["-c", _PRINT_DIR], **env)
    assert res.returncode == 0, res.stderr[-800:]
    assert "DIR=%s" % want.format(tmp=tmp_path) in res.stdout
    assert not os.path.exists(os.path.join(str(tmp_path), "aot", "xla"))


# -- (d) no invented devices ---------------------------------------------------
def test_dryrun_multichip_refuses_more_devices_than_exist():
    import jax

    from __graft_entry__ import dryrun_multichip

    n = len(jax.devices()) + 1
    with pytest.raises(RuntimeError, match="JAX reports %d" % (n - 1)):
        dryrun_multichip(n)
    assert len(jax.devices()) == n - 1
