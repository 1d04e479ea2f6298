"""The share of the router's picks (8 a token a layer, over all the model's experts) that fell on the experts this
chip holds, in percent, over the window's updates (``sheeprl_policy_picks_held_share_sum`` over
``sheeprl_policy_updates_total``): 12.5 where the router is even over 128 experts and 16 are held."""


def read(run):
    family = run.get("family")
    share = family.picks_held_share(run) if hasattr(family, "picks_held_share") else None
    return None if share is None else 100.0 * share
