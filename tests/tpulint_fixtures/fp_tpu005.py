"""tpulint fixture — FALSE positives for TPU005: none of these may fire."""

import os


def respectful():
    plat = os.environ.get("JAX_PLATFORMS", "")  # reading is always fine
    child_env = {**os.environ, "JAX_PLATFORMS": "cpu"}  # subprocess env dict
    os.environ["ESTPU_UNRELATED"] = "1"  # unrelated key
    os.environ.pop("ESTPU_UNRELATED", None)  # unrelated key
    return plat, child_env
