"""Index build (``core/build_engine.py``): distances computed per corpus row.

``build_evals`` of the program's build record (``repro.core.telemetry``
``latest_build``): every distance the wave build computed (the beams'
candidate blocks, the intra-wave block, the reverse-edge scores), over the
corpus rows.  Nothing is read where the program keeps no build record, or
where the record has no count (a builder without waves).
"""


def read(run):
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    latest_build = getattr(telemetry, "latest_build", None)
    rec = latest_build() if latest_build is not None else None
    if rec is None or rec.build_evals is None:
        return None
    return rec.build_evals / run["n"]
