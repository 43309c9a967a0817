"""Race demo: step.check catching an unsynchronized read-modify-write.

Two host threads both run the classic racy counter update

    v = counter.get()          # read
    counter.set(v + tid + 1)   # write computed from a stale read

with no barrier between them, so the two RMWs are unordered in the
happens-before order the checker tracks (only spawn/join edges exist) and the
written values differ per thread — a textbook lost-update race.  Armed via
``Session(check=True)``, the vector-clock detector flags the unordered
read/write and write/write pairs and reports *both* stack sites.

The second half runs the fixed program — same update, but each thread owns a
disjoint round via a DBarrier hand-off — and shows the checker stays silent.

    PYTHONPATH=src python examples/race_demo.py
"""

import jax.numpy as jnp

from repro.core import Session
from repro.utils.compile_cache import enable_compile_cache


def racy():
    sess = Session(backend="host", n_nodes=1, threads_per_node=2, check=True)
    counter = sess.def_global("counter", jnp.float32(0))

    def proc(ctx):
        for _ in range(4):
            v = counter.get()                       # site A: racy read
            counter.set(v + jnp.float32(ctx.tid + 1))   # site B: racy write
        return None

    sess.run(proc)
    findings = sess.findings()
    print(f"racy program: {len(findings)} finding(s)")
    for f in findings:
        print(f"  [{f.kind}] {f.message}")
        for site in f.sites:
            print(f"      site: {site}")
    sess.checker.disable()
    return findings


def synchronized():
    sess = Session(backend="host", n_nodes=1, threads_per_node=2, check=True)
    counter = sess.def_global("counter", jnp.float32(0))
    bar = sess.barrier()

    def proc(ctx):
        # alternate turns: tid 0 updates on even rounds, tid 1 on odd ones,
        # with a barrier between rounds ordering every access pair
        for r in range(4):
            if r % 2 == ctx.tid:
                v = counter.get()
                counter.set(v + jnp.float32(ctx.tid + 1))
            bar.enter()
        return None

    sess.run(proc)
    findings = sess.findings()
    print(f"synchronized program: {len(findings)} finding(s)")
    sess.checker.disable()
    return findings


def main():
    enable_compile_cache()
    racy_findings = racy()
    clean_findings = synchronized()
    assert racy_findings, "the seeded race must be detected"
    assert any({s.split(":")[0] for s in f.sites} and len(f.sites) >= 2
               for f in racy_findings), "both access sites must be reported"
    assert not clean_findings, "the barrier-ordered program must be clean"
    print("ok: race flagged with both sites; synchronized variant clean")


if __name__ == "__main__":
    main()
