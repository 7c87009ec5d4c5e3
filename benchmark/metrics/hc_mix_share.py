"""``mxtpu_hc_mix``'s device time as a share of the decode program's:
the kernel's time inside the runs of the programs that hold
``mxtpu_mla_decode`` over those runs' whole device time
(``hc_mix_roofline.decode_kernel_ms``).  None where no decode program
calls the kernel."""
from benchmark.harness import spec


def read(facts):
    times = spec.load_module("metrics", "hc_mix_roofline").decode_kernel_ms(
        facts)
    return None if times is None else 100.0 * times[0] / times[1]
