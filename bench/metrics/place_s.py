"""Placement: host clock around ``put`` and the view's materialisation,
ending in ``block_until_ready`` (part of set-up; moves ``setup_s``)."""


def read(run):
    return run.setup.get("place_s")
