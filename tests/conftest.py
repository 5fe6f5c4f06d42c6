import os

# Smoke tests and benches must see ONE device (the dry-run sets its own
# 512-device flag inside launch/dryrun.py only).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def _host_events(trace_dir) -> dict[str, list[tuple[tuple[str, int], int, int]]]:
    """``match.*`` events of a ``jax.profiler`` trace's host planes by name,
    as ``(line, start ns, end ns)``; a line is one thread."""
    from pathlib import Path

    import jax

    (path,) = Path(trace_dir).glob("plugins/profile/*/*.xplane.pb")
    pd = jax.profiler.ProfileData.from_serialized_xspace(path.read_bytes())
    out: dict = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for i, ln in enumerate(plane.lines):
                for e in ln.events:
                    if e.name.startswith("match."):
                        out.setdefault(e.name, []).append(
                            ((plane.name, i), e.start_ns, e.start_ns + e.duration_ns)
                        )
    return out


@pytest.fixture
def host_events():
    """Reads the ``match.*`` spans out of a profiler trace directory."""
    return _host_events
