"""Fixtures for the multi-process distributed tier (helpers live in
``dist_scaffold.py`` so test files can import them by name under the bare
``pytest`` entry point)."""
import pytest


@pytest.fixture
def dist_spec(tmp_path):
    """Write a 2-process x 4-device ``launch: local`` spec bound to a fresh
    port; returns a writer callable so phases can rebind ports."""
    def write(port):
        spec = tmp_path / "spec.yml"
        spec.write_text(f"""
launch: local
coordinator: "127.0.0.1:{port}"
nodes:
  - address: proc0
    chief: true
    cpus: [0, 1, 2, 3]
  - address: proc1
    cpus: [0, 1, 2, 3]
""")
        return spec
    return write
