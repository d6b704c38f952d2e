"""The loops' seven contracts (``loop_contracts.py``) by the two models that
hold a share of their router's experts: a KDA state beside the pages, and
window layers with a pool of their own."""
from tests.unit.inference.loop_contracts import *  # noqa: F401,F403
from tests.unit.inference.loop_contracts import rig_fixture

rig = rig_fixture(["tinysolar", "tinytrinity"])
