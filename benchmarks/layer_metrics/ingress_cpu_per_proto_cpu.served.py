"""CPU time of the connection reader threads for each unit of the
protocol threads' CPU time, all replicas of the process together (three
replicas and their readers share ONE GIL): the registry counters
``ingress_cpu_us`` (``Transport._read_loop``: recv, frame decode, queue
put; added every 32 chunks and when a reader ends) over
``proto_cpu_us`` (the sum of every recorder row's ``cpu_us``),
cumulative over the process. It says who ran while a protocol thread
was off the CPU."""

from benchmarks.lib import progcpu


def read(obs):
    return progcpu.ingress_cpu_per_proto_cpu()
