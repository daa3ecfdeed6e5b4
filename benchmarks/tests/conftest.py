import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (HERE, os.path.dirname(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")


import json  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture
def drive(capsys):
    """A whole run of a rehearsal cell through ``run.main`` (the harness's
    look for a chip skipped): returns the result line."""
    import run

    def drive(workload, seed=5):
        rc = run.main(["--rehearsal", "--workload", workload, "--seed", str(seed),
                       "--seconds", "1", "--trace", "0"])
        assert rc == 0
        lines = [line for line in capsys.readouterr().out.splitlines()
                 if line.strip()]
        return json.loads(lines[-1])

    return drive
