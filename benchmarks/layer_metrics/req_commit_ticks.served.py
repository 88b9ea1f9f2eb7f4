"""Mean leader dispatches from a sampled command's ``drain`` to the
dispatch that learned its commit (paxtrace's ``commit`` stamp is that
dispatch's ``t_rb_ns``; the recorder's rows say which dispatches lay
between). A count of dispatches, not a time: the profiler slows the
tick and leaves the count as it is."""

from benchmarks.lib import progobs


def read(obs):
    return progobs.req_ticks("drain", "commit")
