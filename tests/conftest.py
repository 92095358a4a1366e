"""Test harness: an 8-device CPU jax backend stands in for the cluster, the
same way Spark local[n] does in the reference's PipelineContext
(src/test/scala/keystoneml/workflow/PipelineContext.scala:9-25)."""

# Must happen before any test imports jax-using code. Force CPU even when
# the outer environment points at a real accelerator (JAX_PLATFORMS=tpu):
# tests need the 8-device virtual mesh, and a single chip can't provide it.
from keystone_tpu.parallel.virtual import provision_virtual_devices

provision_virtual_devices(8)

# Belt to the provisioner's braces: the XLA:CPU thunk runtime's
# collective rendezvous can hang the whole suite on the oversubscribed
# virtual mesh (see provision_virtual_devices, which opts back into the
# legacy runtime); pinning dispatch synchronous additionally removes
# the async-dispatch reordering the same jaxlib era is known for.
# Compute results and thread-level overlap (scan pipelines, fleets)
# are unaffected — this is the TEST harness configuration.
import jax  # noqa: E402

jax.config.update("jax_cpu_enable_async_dispatch", False)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def pipeline_env():
    """Reset global pipeline state around every test (parity:
    PipelineContext.afterEach resetting PipelineEnv)."""
    from keystone_tpu.workflow.env import PipelineEnv
    from keystone_tpu.workflow.optimizers import clear_memo

    import keystone_tpu.cost as cost
    import keystone_tpu.faults as faults
    import keystone_tpu.obs.flight as flight

    env = PipelineEnv.get_or_create()
    env.reset()
    clear_memo()  # memoized plans pin operator objects; start each test cold
    cost.reset()  # profile store is env-var-memoized like the AOT cache
    faults.clear()  # no fault plan (or stale invocation counters) leaks
    flight.reset()  # each test judges its own bounded flight window
    yield env
    env.reset()
    clear_memo()
    cost.reset()
    faults.clear()
    flight.reset()


@pytest.fixture
def past_first_job():
    """A reset tracer in a process whose first job has closed: the boot
    recorder (``obs.tracer.first_job_spans``) is down, and a span with
    nobody recording is ``NULL_SPAN``. Leaves a reset tracer behind."""
    from keystone_tpu.obs import tracer

    tracer.reset()
    with tracer.span("job"):
        with tracer.span("plan.build"):
            pass
    yield
    tracer.reset()


@pytest.fixture
def bf16_products(monkeypatch):
    """``lax.conv_general_dilated`` as ONE bf16 pass — operands rounded to
    bf16, float32 accumulation — which is how the TPU's default precision
    runs it and how ``ops/conv_rectify_pool.py`` rounds on any backend: a
    test that holds the fused kernel to the XLA bodies rounds both alike."""
    import jax.numpy as jnp

    exact = jax.lax.conv_general_dilated

    def bf16(a):
        return jnp.asarray(a).astype(jnp.bfloat16).astype(jnp.float32)

    def rounded(lhs, rhs, *args, **kw):
        return exact(bf16(lhs), bf16(rhs), *args, **kw)

    monkeypatch.setattr(jax.lax, "conv_general_dilated", rounded)
    yield
    # programs traced under the patch must not serve another test
    from keystone_tpu.compile.segment import reset_dispatchers

    reset_dispatchers()
