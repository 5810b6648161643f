"""Control plane, namespaced-egress cell: seconds of the policy commit
that rendered the node's namespaces at set-up (the configurator's
``render_ms`` counter: rule expansion, renderer commit, epoch swap)."""


def read(run):
    return (run.get("rungs") or {}).get("policy_render_s")
