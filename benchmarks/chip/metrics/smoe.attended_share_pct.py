"""Attended over visible positions, in percent, over the queries of the window's updates: the update's own count
(``sheeprl_policy_attended_share_sum`` over ``sheeprl_policy_updates_total`` between the window's two scrapes).  Under
100 where the indexer's selection bites; 100 where every episode is shorter than ``topk``."""


def read(run):
    family = run.get("family")
    share = family.attended_share(run) if hasattr(family, "attended_share") else None
    return None if share is None else 100.0 * share
