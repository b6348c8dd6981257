"""The host runtime's coordinator blocked on the learner's update,
per interval (``HostConfig(profile=True)``'s ``learner_drain``). Near 0
means the learner hides behind the rollout."""


def read(record):
    prof = record.get("host_profile")
    if not prof or "learner_drain" not in prof:
        return None
    return 1e3 * prof["learner_drain"] / record["intervals"]
