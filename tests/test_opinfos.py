"""The op-correctness matrix: op × dtype(f32/bf16/f16/i32) × (forward | grad
| error-inputs).

Instantiation analog of the reference's ``@ops`` decorator
(``thunder/tests/framework.py:304``) driving its OpInfo DB
(``tests/opinfos.py:315``) — forward outputs and gradients are compared
against torch references for every op in ``tests/opinfos.py``, and every
op's error-input generator must raise the documented exception type (the
reference's error_input_generator axis).
"""
import zlib

import numpy as np
import pytest
import torch

import thunder_tpu as tt

import opinfos as _opinfos
from opinfos import OpInfo, opinfos


@pytest.fixture(autouse=True)
def _a_tests_samples_are_its_own(request):
    # ``opinfos._t`` draws from one generator of its module, so what a test drew depended on the tests its worker
    # ran before it, which ``--dist load`` changes from run to run: ``floor_divide`` in bfloat16 met a quotient a
    # rounding away from a whole number in one of PR 55's whole runs.  A generator a test, seeded by the test's name.
    _opinfos.rng = np.random.default_rng(zlib.crc32(request.node.name.encode()))

_f32_ids = [o.name for o in opinfos]
_bf16_infos = [o for o in opinfos if o.supports_bf16]
_f16_infos = [o for o in opinfos if o.supports_f16 and o.supports_bf16]
_int_infos = [o for o in opinfos if o.supports_int]
_grad_infos = [o for o in opinfos if o.supports_grad]


def _to_torch(x, bf16=False):
    if isinstance(x, np.ndarray):
        t = torch.from_numpy(x.copy())
        if bf16 and t.dtype == torch.float32:
            t = t.to(torch.bfloat16)
        return t
    return x


def _to_np(x):
    if isinstance(x, torch.Tensor):
        return x.detach().to(torch.float32).numpy() if x.dtype == torch.bfloat16 else x.detach().numpy()
    return np.asarray(x, dtype=np.float32) if str(np.asarray(x).dtype) == "bfloat16" else np.asarray(x)


@pytest.mark.parametrize("info", opinfos, ids=_f32_ids)
def test_forward_f32(info: OpInfo):
    samples = info.sample(np.float32)
    targs = [_to_torch(s) for s in samples]
    got = tt.jit(info.op)(*targs)
    ref = info.torch_ref(*[_to_torch(s) for s in samples])
    np.testing.assert_allclose(_to_np(got), _to_np(ref), rtol=info.rtol, atol=info.atol)


@pytest.mark.parametrize("info", _bf16_infos, ids=[o.name for o in _bf16_infos])
def test_forward_bf16(info: OpInfo):
    samples = info.sample(np.float32)
    targs = [_to_torch(s, bf16=True) for s in samples]
    got = tt.jit(info.op)(*targs)
    ref = info.torch_ref(*[_to_torch(s, bf16=True) for s in samples])
    np.testing.assert_allclose(
        _to_np(got), _to_np(ref), rtol=info.bf16_rtol, atol=info.bf16_atol
    )


@pytest.mark.parametrize("info", _f16_infos, ids=[o.name for o in _f16_infos])
def test_forward_f16(info: OpInfo):
    samples = info.sample(np.float32)
    targs = [_to_torch_f16(s) for s in samples]
    got = tt.jit(info.op)(*targs)
    try:
        ref = info.torch_ref(*[_to_torch_f16(s) for s in samples])
    except RuntimeError as e:
        pytest.skip(f"torch cpu has no f16 reference: {e}")
    np.testing.assert_allclose(
        _to_np(got), _to_np(ref), rtol=info.f16_rtol, atol=info.f16_atol
    )


def _to_torch_f16(x):
    if isinstance(x, np.ndarray):
        t = torch.from_numpy(x.copy())
        return t.to(torch.float16) if t.dtype == torch.float32 else t
    return x


@pytest.mark.parametrize("info", _int_infos, ids=[o.name for o in _int_infos])
def test_forward_i32(info: OpInfo):
    samples = info.sample(np.int32)
    got = tt.jit(info.op)(*[_to_torch(s) for s in samples])
    ref = info.torch_ref(*[_to_torch(s) for s in samples])
    np.testing.assert_array_equal(np.asarray(_to_np(got)), _to_np(ref))


@pytest.mark.parametrize("info", opinfos, ids=_f32_ids)
def test_error_inputs(info: OpInfo):
    cases = info.error_inputs()
    assert cases, f"{info.name}: empty error-input generator"
    for case in cases:
        # 4-tuple form carries a custom callable (ops whose registered
        # lambda bakes the offending argument away, e.g. dropout's p)
        fn, (args, exc_type, match) = (info.op, case) if len(case) == 3 else (case[0], case[1:])
        with pytest.raises(exc_type, match=match if match else None):
            tt.jit(fn)(*args)


@pytest.mark.parametrize("info", _grad_infos, ids=[o.name for o in _grad_infos])
def test_grad_f32(info: OpInfo):
    import thunder_tpu.torch as ltorch

    samples = info.sample(np.float32)
    argnums = info.grad_argnums or tuple(
        i for i, s in enumerate(samples) if isinstance(s, np.ndarray) and s.dtype == np.float32
    )
    assert argnums, f"{info.name}: no differentiable inputs in sample"

    def loss(*args):
        out = info.op(*args)
        return ltorch.sum(out)

    val, grads = tt.value_and_grad(loss, argnums=argnums)(*samples)
    if len(argnums) == 1:
        grads = (grads,)

    targs = [
        _to_torch(s).requires_grad_(True) if i in argnums else _to_torch(s)
        for i, s in enumerate(samples)
    ]
    tout = info.torch_ref(*targs)
    tout.sum().backward()

    rtol = info.grad_rtol if info.grad_rtol is not None else max(info.rtol, 1e-4)
    atol = info.grad_atol if info.grad_atol is not None else max(info.atol, 1e-5)
    for gi, argnum in zip(grads, argnums):
        tg = targs[argnum].grad
        assert tg is not None, f"{info.name}: torch produced no grad for arg {argnum}"
        np.testing.assert_allclose(_to_np(gi), _to_np(tg), rtol=rtol, atol=atol, err_msg=f"{info.name} darg{argnum}")


# a smaller executor-matrix slice: the default stack (xla fusion + pallas) vs
# the plain jax operator executor must agree (reference: executor dimension of
# its @ops matrix)
_exec_slice = [o for o in opinfos if o.name in (
    "add", "matmul", "softmax", "layer_norm", "sdpa_causal", "cross_entropy", "gelu", "var_mean",
)]


@pytest.mark.parametrize("info", _exec_slice, ids=[o.name for o in _exec_slice])
def test_executor_stacks_agree(info: OpInfo):
    from thunder_tpu.executors import jaxex

    samples = info.sample(np.float32)
    default = tt.jit(info.op)(*samples)
    jax_only = tt.jit(info.op, executors=[jaxex.ex])(*samples)
    np.testing.assert_allclose(_to_np(default), _to_np(jax_only), rtol=1e-6, atol=1e-7)
