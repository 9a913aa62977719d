"""Gadget-type registry hygiene (VERDICT Weak #7).

advise/* and traceloop ride the legacy CRD start..stop→generate path —
they were mislabeled as PROFILE, which type-keyed handler wiring (agent
+ CLI) silently served with no handlers. Pinned here: the labels, the
loud agent wiring for unknown types, and the run-with-result contract
for every result-typed gadget in the registry.
"""

from __future__ import annotations

import pytest

import inspektor_gadget_tpu.all_gadgets  # noqa: F401 — registers everything
from inspektor_gadget_tpu.agent.service import handlers_for
from inspektor_gadget_tpu.gadgets import registry
from inspektor_gadget_tpu.gadgets.interface import GadgetType


def test_advise_and_traceloop_are_start_stop():
    for cat, name in (("advise", "seccomp-profile"),
                      ("advise", "network-policy"),
                      ("traceloop", "traceloop")):
        desc = registry.get(cat, name)
        assert desc.gadget_type == GadgetType.START_STOP, (
            f"{cat}/{name} registered as {desc.gadget_type}")


def test_profile_label_reserved_for_samplers():
    profiles = [d.full_name for d in registry.get_all()
                if d.gadget_type == GadgetType.PROFILE]
    assert sorted(profiles) == ["profile/block-io", "profile/cpu"]


def test_every_registered_type_has_agent_wiring():
    """The agent must know how to serve every gadget in the registry —
    a new type that reaches the registry without handler wiring is a
    silently-empty stream waiting to happen."""
    sentinel_ev, sentinel_arr = object(), object()
    for desc in registry.get_all():
        ev, arr = handlers_for(desc.gadget_type, {"json"},
                               sentinel_ev, sentinel_arr)
        if desc.gadget_type == GadgetType.TRACE:
            assert ev is sentinel_ev
        elif desc.gadget_type == GadgetType.TRACE_INTERVALS:
            assert arr is sentinel_arr
        else:
            assert ev is None


def test_unknown_type_raises_loudly():
    with pytest.raises(ValueError, match="no handler wiring"):
        handlers_for("holographic", {"json"}, None, None)


def test_one_shot_combiner_gating():
    ev, arr = handlers_for(GadgetType.ONE_SHOT, {"json", "combiner"},
                           "E", "A")
    assert (ev, arr) == (None, "A")
    ev, arr = handlers_for(GadgetType.ONE_SHOT, {"json"}, "E", "A")
    assert (ev, arr) == (None, None)


def test_trace_rows_are_decoded_only_for_a_json_subscriber():
    """A summary- or batch-only run gets no per-event handler: wiring one
    makes the run loop decode every event of every batch into a Python
    row that nobody receives (on the chip that held a summary-only agent
    run to a seventh of the LocalRuntime run's step rate, PR 21)."""
    assert handlers_for(GadgetType.TRACE, {"json", "summary"},
                        "E", "A") == ("E", None)
    assert handlers_for(GadgetType.TRACE, {"summary", "batch"},
                        "E", "A") == (None, None)


def test_result_typed_gadgets_implement_run_with_result():
    """Every PROFILE/START_STOP gadget class must expose run_with_result
    — the local runtime now refuses to run one that doesn't (the caller
    would otherwise wait on a result that never comes)."""
    from inspektor_gadget_tpu.gadgets import GadgetContext
    for desc in registry.get_all():
        if desc.gadget_type not in (GadgetType.PROFILE,
                                    GadgetType.START_STOP):
            continue
        ctx = GadgetContext(desc, gadget_params=desc.params().to_params())
        gadget = desc.new_instance(ctx)
        assert hasattr(gadget, "run_with_result"), desc.full_name


def test_local_runtime_rejects_result_type_without_impl():
    from inspektor_gadget_tpu.gadgets import GadgetContext
    from inspektor_gadget_tpu.gadgets.interface import GadgetDesc
    from inspektor_gadget_tpu.runtime.local import LocalRuntime

    class Broken:
        def run(self, ctx):  # streams, despite the result-typed label
            pass

    class BrokenDesc(GadgetDesc):
        name = "broken"
        category = "test"
        gadget_type = GadgetType.START_STOP

        def new_instance(self, ctx):
            return Broken()

    ctx = GadgetContext(BrokenDesc())
    result = LocalRuntime().run_gadget(ctx)
    errs = result.errors()
    assert errs and "run_with_result" in str(errs)
