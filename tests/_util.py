"""Side-effect-free helpers shared by test modules (importing conftest
directly would re-execute its env/jax.config side effects as a second
module object)."""


def write_convergence_log(record):
    """Append one record to the committed convergence artifact when
    MXTPU_WRITE_CONVERGENCE_LOG is set (shared by the train-suite gates)."""
    import json
    import os
    out = os.environ.get("MXTPU_WRITE_CONVERGENCE_LOG")
    if out:
        with open(out, "a") as f:
            f.write(json.dumps(record) + "\n")


def profiled_spans(run, trace_dir, prefixes):
    """Run ``run()`` inside a profiler session started by
    ``jax.profiler.start_trace`` directly (NOT ``mx.profiler.start``) and
    return the host-plane events whose name starts with one of
    ``prefixes``: ``[(name, start_ns, end_ns, {stat: value}, thread)]``."""
    import glob
    import os

    import jax
    jax.profiler.start_trace(str(trace_dir))
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb"))
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(tuple(prefixes)):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats), line.name))
    return out


def assert_spans_nest(spans, child, parent):
    """Every ``child`` span lies inside a ``parent`` span of its thread."""
    parents = [s for s in spans if s[0] == parent]
    children = [s for s in spans if s[0] == child]
    assert children and parents, (child, parent, sorted({s[0] for s in spans}))
    for _, start, end, _, thread in children:
        assert any(p[1] <= start and end <= p[2] and p[4] == thread
                   for p in parents), (child, "outside every", parent)


def lowered_with_and_without_scopes(lower, monkeypatch):
    """``lower()`` -> a ``jax.stages.Lowered``.  Returns its text with
    debug info (where ``jax.named_scope`` names show) after checking that
    the program itself — the text without locations — is byte-identical to
    the one lowered with every ``jax.named_scope`` a no-op: a scope is
    metadata, it moves no operation and invalidates no executable."""
    import contextlib

    import jax
    named = lower()

    @contextlib.contextmanager
    def no_scope(name):
        yield

    with monkeypatch.context() as patch:
        patch.setattr(jax, "named_scope", no_scope)
        bare = lower()
    assert named.as_text() == bare.as_text()
    assert "mx." not in bare.as_text(debug_info=True)
    return named.as_text(debug_info=True)


def without_kernel_locations(text):
    """A lowered program's text with each Mosaic kernel's serialized body
    (which carries source lines) replaced by its MLIR text without
    locations."""
    import base64
    import re
    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    def body(match):
        ctx = jax_mlir.make_ir_context()
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True
        with ctx:
            module = ir.Module.parse(base64.b64decode(match.group(1)))
            return module.operation.get_asm(enable_debug_info=False)

    return re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', body, text)
