"""repro: LABOR layer-neighbor sampling, production-scale JAX framework."""
__version__ = "1.0.0"

import os as _os

#: root of the source checkout (src/repro/__init__.py -> two levels up):
#: where the tuning cache and the default compile cache live, so that
#: what a run dispatches depends only on files of the checkout
CHECKOUT_DIR = _os.path.dirname(_os.path.dirname(_os.path.dirname(
    _os.path.abspath(__file__))))
