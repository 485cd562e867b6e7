"""Per-kernel shape/dtype sweeps vs. the ref.py pure-jnp oracles
(interpret=True executes the Pallas bodies on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.graphir import pattern_from_spec
from repro.kernels import (attention, default_interpret, fused_pe_apply,
                           matmul_fused, selective_scan)
from repro.kernels.ref import (ref_attention, ref_gemm_pe, ref_mamba_scan,
                               ref_pe)

RNG = np.random.default_rng(42)

PE_PATTERNS = {
    "muladd": pattern_from_spec([("mul", (-1, -1)), ("add", (0, -1))]),
    "conv_relu": pattern_from_spec([("mul", (-1, -1)), ("add", (0, -1)),
                                    ("const", ()), ("max", (1, 2))]),
    "harris_resp": pattern_from_spec([("mul", (-1, -1)), ("mul", (-1, -1)),
                                      ("sub", (0, 1)), ("abs", (2,))]),
    "swiglu_core": pattern_from_spec([("sigmoid", (-1,)), ("mul", (0, -1)),
                                      ("mul", (1, -1))]),
}


@pytest.mark.parametrize("name", sorted(PE_PATTERNS))
@pytest.mark.parametrize("shape", [(16, 16), (33, 77), (128, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pe_fused_sweep(name, shape, dtype):
    pat = PE_PATTERNS[name]
    from repro.graphir.graph import free_in_ports
    n_in = len(free_in_ports(pat))
    xs = [jnp.asarray(RNG.uniform(-1.5, 1.5, shape), dtype)
          for _ in range(n_in)]
    got = fused_pe_apply(pat, *xs, block=(64, 128), interpret=True)
    exp = ref_pe(pat, *[np.asarray(x, np.float64) for x in xs])
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float64), exp,
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("s,hq,hkv,d", [(128, 4, 4, 32), (256, 4, 2, 32),
                                        (192, 8, 2, 16)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(s, hq, hkv, d, causal):
    q = jnp.asarray(RNG.normal(size=(2, hq, s, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(2, hkv, s, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(2, hkv, s, d)), jnp.float32)
    got = attention(q, k, v, causal=causal, bq=64, bk=64, interpret=True)
    exp = ref_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window,softcap", [(0, 0.0), (48, 0.0), (0, 30.0),
                                            (64, 20.0)])
def test_flash_attention_window_softcap(window, softcap):
    s, hq, hkv, d = 256, 4, 2, 32
    q = jnp.asarray(RNG.normal(size=(1, hq, s, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, hkv, s, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(1, hkv, s, d)), jnp.float32)
    got = attention(q, k, v, causal=True, window=window, softcap=softcap,
                    bq=64, bk=64, interpret=True)
    exp = ref_attention(q, k, v, causal=True, window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16():
    s, hq, hkv, d = 128, 4, 2, 32
    q = jnp.asarray(RNG.normal(size=(1, hq, s, d)), jnp.bfloat16)
    k = jnp.asarray(RNG.normal(size=(1, hkv, s, d)), jnp.bfloat16)
    v = jnp.asarray(RNG.normal(size=(1, hkv, s, d)), jnp.bfloat16)
    got = attention(q, k, v, causal=True, bq=64, bk=64, interpret=True)
    exp = ref_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(exp, np.float32),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("s,d,n", [(64, 32, 4), (96, 64, 8), (128, 128, 16)])
def test_mamba_scan_sweep(s, d, n):
    b = 2
    a = jnp.asarray(RNG.uniform(0.6, 0.999, (b, s, d, n)), jnp.float32)
    bx = jnp.asarray(RNG.normal(size=(b, s, d, n)) * 0.1, jnp.float32)
    c = jnp.asarray(RNG.normal(size=(b, s, n)), jnp.float32)
    got = selective_scan(a, bx, c, bs=32, bd=32, interpret=True)
    exp = ref_mamba_scan(a, bx, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("m,k,n", [(64, 64, 64), (100, 70, 50),
                                   (256, 128, 192)])
def test_gemm_plain_sweep(m, k, n):
    x = jnp.asarray(RNG.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(k, n)), jnp.float32)
    got = matmul_fused(x, w, bm=64, bn=64, bk=64, interpret=True)
    exp = ref_gemm_pe(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=1e-4, atol=1e-4)


def test_gemm_bias_relu_epilogue():
    epi = pattern_from_spec([("add", (-1, -1)), ("const", ()),
                             ("max", (0, 1))])
    x = jnp.asarray(RNG.normal(size=(100, 70)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(70, 50)), jnp.float32)
    bias = jnp.asarray(RNG.normal(size=(50,)), jnp.float32)
    got = matmul_fused(x, w, bias, epilogue=epi, extra_kinds=("vec",),
                       bm=64, bn=64, bk=64, interpret=True)
    exp = ref_gemm_pe(x, w, bias, epilogue=epi, extra_kinds=("vec",))
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=1e-4, atol=1e-4)


def test_gemm_residual_epilogue():
    epi = pattern_from_spec([("add", (-1, -1))])      # acc + residual
    x = jnp.asarray(RNG.normal(size=(64, 64)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(64, 64)), jnp.float32)
    res = jnp.asarray(RNG.normal(size=(64, 64)), jnp.float32)
    got = matmul_fused(x, w, res, epilogue=epi, extra_kinds=("full",),
                       bm=32, bn=32, bk=32, interpret=True)
    exp = ref_gemm_pe(x, w, res, epilogue=epi, extra_kinds=("full",))
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=1e-4, atol=1e-4)


def test_mined_pattern_to_kernel_end_to_end():
    """DSE output drives kernel generation: mine the conv app, take the top
    subgraph, generate the fused kernel, check against the oracle."""
    from repro.core import MiningConfig, mine_and_rank
    from repro.graphir import trace_scalar

    def conv4(i0, i1, i2, i3, w0, w1, w2, w3, c):
        return (((i0 * w0) + (i1 * w1)) + (i2 * w2)) + (i3 * w3) + c
    g = trace_scalar(conv4, ["i0", "i1", "i2", "i3",
                             "w0", "w1", "w2", "w3", "c"])
    ranked = mine_and_rank(g, MiningConfig(min_support=2,
                                           max_pattern_nodes=4))
    pat = ranked[0].pattern
    from repro.graphir.graph import free_in_ports
    n_in = len(free_in_ports(pat))
    xs = [jnp.asarray(RNG.uniform(0.5, 1.5, (32, 64)), jnp.float32)
          for _ in range(n_in)]
    got = fused_pe_apply(pat, *xs, block=(32, 64), interpret=True)
    exp = ref_pe(pat, *[np.asarray(x, np.float64) for x in xs])
    outs = got if isinstance(got, tuple) else (got,)
    exps = exp if isinstance(exp, tuple) else (exp,)
    for o, e in zip(outs, exps):
        np.testing.assert_allclose(np.asarray(o, np.float64), e, rtol=1e-5)


def test_default_interpret_only_on_cpu(monkeypatch):
    assert default_interpret() is True            # the tests' cpu platform
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert default_interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        default_interpret()


@pytest.mark.parametrize("kernel", ["hpwl_pallas", "alu_step_pallas"])
def test_kernel_interpret_default_follows_platform(kernel, monkeypatch):
    # a kernel called without interpret= never picks the interpreter on
    # its own: an unknown platform is refused at trace time
    from repro.kernels.pnr_cost import hpwl_pallas
    from repro.kernels.sim_step import alu_step_pallas

    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        if kernel == "hpwl_pallas":     # an (N, D) shape no other test uses
            hpwl_pallas(jnp.zeros((5, 2)), jnp.zeros((3, 7), jnp.int32),
                        jnp.ones((3, 7), bool))
        else:
            x = jnp.zeros((3, 5))
            alu_step_pallas(jnp.zeros((5,), jnp.int32), x, x, x,
                            ("nop", "add"))
