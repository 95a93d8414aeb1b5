"""The harness's own tests. Run by hand from the repository's root:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider

They are not part of the repository's tier-1 suite (which collects tests/),
and no test here needs a chip."""

import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))
