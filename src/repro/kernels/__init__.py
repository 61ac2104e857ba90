"""CRAM-PM TPU kernels: Pallas implementations + jnp oracles.

Perf-critical compute hot-spots of the paper's workload, adapted to the TPU
memory hierarchy (see DESIGN.md Sec. 2):

* ``match_swar``  -- VPU bit-parallel sliding match (2-bit packed SWAR).
* ``match_mxu``   -- MXU one-hot correlation matcher (batched patterns).
* ``popcount``    -- bulk bitcount (the Fig. 4b adder tree, SWAR form).
* ``bitwise``     -- bulk NOT/OR/NAND/XOR (Fig. 11 gate-level analogue).

``ref`` holds the pure-jnp oracles.  Matching workloads enter through the
engine layer ``repro.match`` (planner + device-resident packed corpus +
streaming executor; DESIGN.md Sec. 3); ``ops`` keeps thin one-shot compat
wrappers plus the bulk-op entry points.
"""


def default_interpret() -> bool:
    """Whether Pallas kernels run in interpret mode by default.

    The one switch for the whole stack: compiled Mosaic on a TPU, the
    Pallas interpreter anywhere else (how CPU test runs execute the kernel
    bodies).  Every ``interpret=None`` argument resolves through here.
    """
    import jax
    return jax.default_backend() != "tpu"
