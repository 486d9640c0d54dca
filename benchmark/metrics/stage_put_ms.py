"""Host time inside the staging's ``jax.device_put`` calls until they return
(``RoundRecord.stage["put_s"]``, the ``driver.stage.put`` span: slicing by
the sharding, host copies, the enqueue), mean over the window's rounds. What
is left of ``stage_hidden_ms`` is the wait for the bytes to land
(``driver.stage.land``, a device at a time). Silent on a program without
``stage``."""


def read(run):
    split = [getattr(r, "stage", None) for r in run["records"]]
    if not split or not all(split):
        return None
    return 1e3 * sum(s["put_s"] for s in split) / len(split)
