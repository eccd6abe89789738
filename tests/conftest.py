import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running integration tests")


@pytest.fixture
def profiled(tmp_path):
    """``profiled(fn)`` runs ``fn`` under the JAX profiler and returns
    the host spans named ``serve.*`` that it recorded, as ``(name, t0,
    t1, stats)`` in start order."""
    import glob

    import jax
    from jax.profiler import ProfileData

    def run(fn):
        jax.profiler.start_trace(str(tmp_path))
        try:
            fn()
        finally:
            jax.profiler.stop_trace()
        path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                          recursive=True)
        out = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                dict(e.stats))
               for plane in ProfileData.from_file(path).planes
               for line in plane.lines for e in line.events
               if e.name.startswith("serve.")]
        return sorted(out, key=lambda s: (s[1], -s[2]))
    return run
