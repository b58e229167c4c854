"""Host milliseconds of one park plus one resume: the mean
``ServingEngine.park`` and the mean ``Router.follow_up`` that resumes a
parked session, each ended when the pool is ready, over the window."""


def read(r):
    park, resume = r.spans.get("park"), r.spans.get("resume")
    if not park or not resume:
        return None
    return 1e3 * (sum(park) / len(park) + sum(resume) / len(resume))
