"""chip_smoke.py off the chip: it must refuse, loudly and at once; its
rehearsal must run every phase at tiny sizes and claim nothing."""
import os
import re
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(*args, **env):
    full = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    full.pop("XLA_FLAGS", None)      # the script provisions its own devices
    t0 = time.monotonic()
    r = subprocess.run([sys.executable, SMOKE, *args], capture_output=True,
                       text=True, timeout=600, env=full, cwd=ROOT)
    return r, time.monotonic() - t0


def _result_lines(stdout):
    return [ln for ln in stdout.splitlines() if ln.startswith("{")]


def test_refuses_a_machine_without_a_tpu():
    r, dt = _run(MXNET_TPU_PALLAS_INTERPRET="0")
    assert r.returncode != 0
    assert dt < 60, f"took {dt:.0f}s to notice there is no chip"
    # the device line comes first, the refusal names what was found
    assert r.stdout.splitlines()[0].startswith("jax ")
    assert "platform=cpu" in r.stdout
    assert "platform 'cpu'" in r.stderr and "JAX_PLATFORMS='cpu'" in r.stderr
    assert not _result_lines(r.stdout)


def test_refuses_the_interpreter():
    r, _ = _run(MXNET_TPU_PALLAS_INTERPRET="1")
    assert r.returncode != 0
    assert "MXNET_TPU_PALLAS_INTERPRET" in r.stderr
    assert not _result_lines(r.stdout)


@pytest.mark.slow  # ~30 s of the 870 s tier-1 budget; ci sanity runs it by name
def test_rehearsal_runs_every_phase_and_claims_nothing():
    r, _ = _run("--rehearsal")
    assert r.returncode == 0, r.stderr[-3000:]
    out = r.stdout
    for name in ("train_one_chip", "train_four_chips", "serve_one_chip",
                 "kernels"):
        assert f"== {name} ==" in out and f"-- {name}:" in out, name
    assert "REHEARSAL complete" in out.splitlines()[-1]
    assert "first-step loss, dropout 0" in out        # the 1-vs-4 comparison
    assert out.count("state='done'") == 10    # every request DONE: two waves
    # of four through GPT-2, one through Laguna's two page classes, one
    # through DeepSeek-V2's latent cache
    assert not _result_lines(out)                     # no result line
    assert not re.search(r"\bpass(ed|es)?\b", out, re.I)


def test_pallas_kernels_reads_names_from_a_lowered_module():
    sys.path.insert(0, ROOT)
    from tools.tpu_validate import pallas_kernels

    class Lowered:
        def as_text(self, debug_info=False):
            assert debug_info
            return "\n".join([
                '#loc1 = loc("x")',
                '#loc7 = loc("jit(step)/jvp(flash_fwd)/pallas_call"(#loc1))',
                '#loc8 = loc("jit(step)/transpose(jvp(flash_dq))/pallas_call"'
                '(#loc1))',
                '#loc9 = loc("jit(step)/shard_map/flash_fwd/pallas_call"'
                '(#loc1))',
                '#loc10 = loc("jit(step)/lamb_pass1/pallas_call"(#loc1))',
                '  %1 = stablehlo.custom_call @tpu_custom_call(%0) {} : '
                '(tensor<8xf32>) -> tensor<8xf32> loc(#loc7)',
                '  %2 = stablehlo.custom_call @tpu_custom_call(%1) {} : '
                '(tensor<8xf32>) -> tensor<8xf32> loc(#loc8)',
                '  %3 = stablehlo.custom_call @tpu_custom_call(%2) {} : '
                '(tensor<8xf32>) -> tensor<8xf32> loc(#loc9)',
                '  %4 = stablehlo.custom_call @tpu_custom_call(%3) {} : '
                '(tensor<8xf32>) -> tensor<8xf32> loc(#loc10)',
                '  %5 = stablehlo.custom_call @Sharding(%4) {} : '
                '(tensor<8xf32>) -> tensor<8xf32> loc(#loc1)',
            ])

    assert pallas_kernels(Lowered()) == {"flash_fwd": 2, "flash_dq": 1,
                                         "lamb_pass1": 1}


def test_pallas_kernels_counts_a_shared_function_once_a_call():
    """A stack of layers traced once (`models/_decode.layer_call`) is one
    function in the module and a call a layer: its kernels count a call,
    through calls of calls too."""
    sys.path.insert(0, ROOT)
    from tools.tpu_validate import pallas_kernels

    class Lowered:
        def as_text(self, debug_info=False):
            return "\n".join([
                '#loc1 = loc("x")',
                '#loc7 = loc("jit(pure)/jit(layer)/paged_attention/'
                'pallas_call"(#loc1))',
                '#loc8 = loc("jit(pure)/jit(layer)/kv_page_write/'
                'pallas_call"(#loc1))',
                '#loc9 = loc("jit(pure)/head/pallas_call"(#loc1))',
                '  func.func public @main(%arg0: tensor<8xf32>) {',
                '    %1:2 = call @layer(%arg0) : (tensor<8xf32>) -> '
                'tensor<8xf32> loc(#loc1)',
                '    %2:2 = call @layer(%1) : (tensor<8xf32>) -> '
                'tensor<8xf32> loc(#loc1)',
                '    %3 = call @twice(%2) : (tensor<8xf32>) -> '
                'tensor<8xf32> loc(#loc1)',
                '    %4 = stablehlo.custom_call @tpu_custom_call(%3) {} : '
                '(tensor<8xf32>) -> tensor<8xf32> loc(#loc9)',
                '  }',
                '  func.func private @layer(%arg0: tensor<8xf32>) {',
                '    %1 = stablehlo.custom_call @tpu_custom_call(%arg0) {} '
                ': (tensor<8xf32>) -> tensor<8xf32> loc(#loc8)',
                '    %2 = stablehlo.custom_call @tpu_custom_call(%1) {} : '
                '(tensor<8xf32>) -> tensor<8xf32> loc(#loc7)',
                '  }',
                '  func.func private @twice(%arg0: tensor<8xf32>) {',
                '    %1 = call @layer(%arg0) : (tensor<8xf32>) -> '
                'tensor<8xf32> loc(#loc1)',
                '    %2 = call @layer(%1) : (tensor<8xf32>) -> '
                'tensor<8xf32> loc(#loc1)',
                '  }',
            ])

    assert pallas_kernels(Lowered()) == {"paged_attention": 4,
                                         "kv_page_write": 4, "head": 1}
