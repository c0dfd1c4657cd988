"""Test environment: force an 8-device CPU platform before JAX initializes.

This is the TPU-world substitute for a fake distributed backend
(SURVEY.md §4): all sharding/collective tests run against a virtual
8-device host mesh.
"""

import collections
import os
import re

# SPTPU_TEST_PLATFORM=tpu runs hardware-gated tests (e.g. the in-kernel
# dropout suite — interpret-mode pltpu.prng_random_bits is a zero stub)
# against the real chip instead of the virtual CPU mesh.
_platform = os.environ.get("SPTPU_TEST_PLATFORM", "cpu")
os.environ["JAX_PLATFORMS"] = _platform
_flags = os.environ.get("XLA_FLAGS", "")
if _platform == "cpu" and "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
if _platform == "cpu":
    # The CPU client sizes its one thread pool max(cores, devices), and a
    # step with collectives parks one pool thread per device until all eight
    # have joined. On an 8-core host that is the whole pool: one more blocking
    # item (a device-to-device copy of the next batch waiting on device 0)
    # keeps the eighth participant off the pool for good, and XLA aborts the
    # process after 40 s ("Termination timeout for all reduce"). It needs a
    # loaded host to run that far ahead — six xdist workers are one. Size the
    # pool past the device count so a collective can always be joined. And
    # past TWICE the device count since PR 48: the CPU client runs
    # independent parts of a step at once, and keye_vl's indexer loss is a
    # side branch of its layer since the attention left its loops (its
    # gradients' all-reduce ran beside the expert layer's all-gather, six
    # devices parked two threads each, twelve, and the other two never got
    # one: `test_cli_train_runs_the_family...` aborted two runs in three).
    os.environ.setdefault("PJRT_NPROC", "24")

import jax  # noqa: E402
import numpy as np  # noqa: E402

# The suite leaves JAX's persistent compilation cache off: entry points under
# test (cli.main) point it at <repo>/.jax_cache, and tests must neither read
# nor fill that directory.
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    if len(devs) != 8:
        pytest.skip(f"needs the 8-virtual-device CPU mesh, have {len(devs)}")
    return devs


def equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it (a remat's
    body, a custom rule's, a jit's), a kernel's own body apart."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from equations(sub)


def kernel_calls(fn, *args):
    """How many `pallas_call`s of each `name=` the traced `fn` holds."""
    return collections.Counter(
        eqn.params["name"]
        for eqn in equations(jax.make_jaxpr(fn)(*args).jaxpr)
        if eqn.primitive.name == "pallas_call")


def checkpoint_names(fn, *args):
    """(name, shape) of every `checkpoint_name` in the traced `fn`."""
    return sorted(
        (eqn.params["name"], eqn.outvars[0].aval.shape)
        for eqn in equations(jax.make_jaxpr(fn)(*args).jaxpr)
        if eqn.primitive.name == "name")


def two_remat_layers(layer, keep=None):
    """`value_and_grad` over (w, x) of two stacked calls of `layer(w, x)`,
    each under `jax.checkpoint(..., prevent_cse=True)` as the families wrap
    a layer; with `keep`, the remat's policy saves those names only."""
    policy = {} if keep is None else {
        "policy": jax.checkpoint_policies.save_only_these_names(*keep)}
    remat = jax.checkpoint(layer, prevent_cse=True, **policy)
    return jax.value_and_grad(
        lambda w, x: (remat(w, remat(w, x)) ** 2).sum(), argnums=(0, 1))


def assert_same_bits(got, want):
    """Two trees, leaf for leaf, bit for bit."""
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        np.testing.assert_array_equal(a, b)


def keep_against_plain_remat(monkeypatch, make_program, tree, kernels):
    """A model's loss-and-gradient program under its family's `nn.remat`
    policy against the same under a plain `nn.remat(..., prevent_cse=True)`:
    every leaf bit for bit; returns the two programs' `pallas_call` counts of
    `kernels`. `make_program` builds a NEW function a call: JAX keeps a
    trace by the function."""
    from flax import linen as nn

    def run():
        program = make_program()
        calls = kernel_calls(program, tree)
        return jax.jit(program)(tree), tuple(calls[k] for k in kernels)

    kept, n_kept = run()
    remat = nn.remat
    monkeypatch.setattr(nn, "remat", lambda cls, policy, **kw: remat(cls, **kw))
    plain, n_plain = run()
    assert_same_bits(kept, plain)
    return n_kept, n_plain


def kernel_passes(text, scopes, kernels, layer):
    """{kernel name: the passes ("fwd", "remat", "bwd") its top-level
    instructions stand in} of a compiled step's `text`, for the Pallas
    kernels whose `name=` matches the pattern `kernels`; `scopes` is
    `hlo_cost.device_scopes(text)`. A kernel's `name=` is no layer: its
    time stays the rule's own scope's, `layer`."""
    seen = {}
    for m in re.finditer(
            r"%?([\w.\-]+) = [^\n]*op_name=\"[^\"]*(" + kernels + ")", text):
        if m.group(1) not in scopes:  # a constant
            continue
        s = scopes[m.group(1)]
        assert s.layer == layer, m.group(0)
        if s.top_level:
            seen.setdefault(m.group(2), set()).add(s.pass_)
    return seen


def assert_no_leaks(eng):
    """The serve engine's drained-pool leak invariant, shared across
    the paged-pool, fault/quarantine and degradation suites (apply
    after every test drain): every slot back on the free list with a
    consistent `_free_mask`; on paged pools every page back on the free
    list (the prefix tree — the one legitimate post-drain holder — is
    fully evicted first), the refcount sum back at the trash page's
    permanent 1, and the free list exactly the zero-refcount pages; on
    quantized pools the exact-lane free list intact."""
    pool = eng.pool
    assert pool.n_active == 0, "slots still active after drain"
    assert pool._free_mask.all(), "slot leaked (_free_mask inconsistent)"
    assert sorted(pool._free) == list(range(pool.n_slots)), \
        "slot free list leaked or duplicated"
    assert all(r is None for r in eng._slot_req), \
        "engine slot mirror still holds a request"
    if eng.prefix_cache is not None:
        while eng.prefix_cache.evict_one():
            pass
    if hasattr(pool, "refcount"):  # paged pool
        assert pool.pages_free == pool.page_budget, (
            f"pages leaked: {pool.pages_free} free of "
            f"{pool.page_budget} budgeted"
        )
        assert int(pool.refcount.sum()) == 1, (
            "refcounts leaked (expected only the trash page's "
            f"permanent hold): sum={int(pool.refcount.sum())}"
        )
        free = set(pool._free_pages)
        zero = {p for p in range(1, pool.n_pages)
                if pool.refcount[p] == 0}
        assert free == zero, "free list != zero-refcount pages"
        assert len(pool._free_pages) == len(free), "duplicate free entries"
    if getattr(pool, "exact_lanes", 0):
        assert sorted(eng._exact_free) == list(
            range(1, pool.exact_lanes + 1)
        ), "exact-lane free list leaked"
