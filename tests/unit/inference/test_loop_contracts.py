"""The loops' seven contracts (``loop_contracts.py``) by the models whose
layers keep pages and nothing beside them: plain K/V pages, expert layers,
a pass loop, latent rows, an indexer's keys."""
from tests.unit.inference.loop_contracts import *  # noqa: F401,F403
from tests.unit.inference.loop_contracts import rig_fixture

rig = rig_fixture(["tinymistral", "tinyolmoe", "tinyouro", "tinyglm",
                   "tinykeye"])
