"""Mean leader dispatches from the one that learned a sampled command's
commit to the serialization of its reply (paxtrace ``commit`` to
``reply_ser``, counted in the recorder's ``t_rb_ns``). A count of
dispatches: the profiler leaves it as it is."""

from benchmarks.lib import progobs


def read(obs):
    return progobs.req_ticks("commit", "reply_ser")
