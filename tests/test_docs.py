"""Documentation contract: every public export of ``repro.serving`` and
``repro.memory`` — and every public method/property defined on an
exported class — carries a docstring (the PR-4 docs acceptance bar;
docs/TELEMETRY.md is the prose counterpart)."""

import inspect

import pytest

import repro.memory
import repro.serving

MODULES = (repro.serving, repro.memory)


def _exported_objects():
    for mod in MODULES:
        for name in mod.__all__:
            yield mod.__name__, name, getattr(mod, name)


def _public_members(cls):
    """Callables and properties defined directly in ``cls``'s body
    (inherited members are checked on the class that defines them)."""
    for name, member in vars(cls).items():
        if name.startswith("_"):
            continue
        if isinstance(member, (staticmethod, classmethod)):
            member = member.__func__
        if isinstance(member, property) or callable(member):
            yield name, member


def test_every_public_export_has_a_docstring():
    missing = []
    for mod_name, name, obj in _exported_objects():
        if isinstance(obj, (str, tuple, dict, list, int, float)):
            continue                       # constants (e.g. PIPELINES)
        if not inspect.getdoc(obj):
            missing.append(f"{mod_name}.{name}")
    assert not missing, f"exports without docstrings: {missing}"


def test_every_public_method_of_exported_classes_has_a_docstring():
    missing = []
    for mod_name, name, obj in _exported_objects():
        if not inspect.isclass(obj):
            continue
        for mname, member in _public_members(obj):
            doc = (member.fget.__doc__ if isinstance(member, property)
                   else getattr(member, "__doc__", None))
            if not doc:
                missing.append(f"{mod_name}.{name}.{mname}")
    assert not missing, \
        f"public methods/properties without docstrings: {missing}"


def test_docstrings_name_units_on_key_surfaces():
    """Spot-check that the load-bearing quantitative surfaces state
    their units (seconds / bytes / pages), per the docs acceptance
    criterion."""
    from repro.memory import DevicePagePool, MemoryLedger
    from repro.serving import RagResponse

    assert "second" in (RagResponse.latency_s.fget.__doc__ or "").lower()
    assert "bytes" in (MemoryLedger.__doc__ or "").lower()
    assert "page" in (DevicePagePool.__doc__ or "").lower()
