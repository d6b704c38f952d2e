"""The loops' seven contracts (``loop_contracts.py``) by the models that keep
per-slot state beside their pages: convolution rows, a lightning state, a
gated-delta state."""
from tests.unit.inference.loop_contracts import *  # noqa: F401,F403
from tests.unit.inference.loop_contracts import rig_fixture

rig = rig_fixture(["tinylfm2", "tinysala", "tinyolmohybrid"])
