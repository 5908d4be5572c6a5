"""The network scopes' readers (``ofl_bank_ms``, ``ofl_gen_net_ms``,
``ofl_server_ms``, ``ofl_dhs_ms``) and the scope test they share: on
hand-made traces, and on the op names of the epoch program compiled at tiny
sizes on the CPU."""
import pytest

import benchpath  # noqa: F401
from benchlib import common, scopes, trace as tr

READERS = {
    "ofl_bank_ms": "ofl.bank",
    "ofl_gen_net_ms": "ofl.gen.net",
    "ofl_server_ms": "ofl.server",
    "ofl_dhs_ms": "ofl.dhs",
}


def reader(name):
    return common.load_module(common.BENCH / "metrics" / f"{name}.py")


@pytest.mark.parametrize("path, scope, hit", [
    ("jit(epoch_step)/ofl.kd/while/body/closed_call/ofl.dhs/jit(norm)", "ofl.dhs", True),
    ("jit(epoch_step)/ofl.gen.boost/while/body/closed_call/jvp(ofl.bank)/ofl.bank.g0/vmap()/conv", "ofl.bank", True),
    ("jit(epoch_step)/ofl.gen.boost/while/body/closed_call/transpose(jvp(ofl.bank))/ofl.bank.g0/vmap()", "ofl.bank", True),
    ("jit(epoch_step)/ofl.gen.boost/while/body/closed_call/transpose(jvp(ofl.bank))/ofl.bank.g0/vmap()", "ofl.bank.g0", True),
    ("jit(f)/vmap(ofl.server)/dot_general", "ofl.server", True),
    ("transpose(jvp(ofl.gen.net))", "ofl.gen.net", True),
    ("jit(f)/x/reshape;ofl.dhs/jvp(ofl.bank)/vmap()", "ofl.dhs", True),
    ("jit(epoch_step)/ofl.kd/ensemble_kl_bwd/pallas_call", "ensemble_kl_bwd", True),
    ("jit(f)/ofl.bankx/conv", "ofl.bank", False),
    ("jit(f)/jvp(ofl.bankx)/conv", "ofl.bank", False),
    ("jit(f)/ofl.bank.g0/conv", "ofl.bank", False),
    ("jit(f)/myofl.bank/conv", "ofl.bank", False),
    ("jit(f)/ofl.gen.boost/conv", "ofl.gen.net", False),
    ("jit(f)/ofl.server_x/conv", "ofl.server", False),
    ("", "ofl.bank", False),
])
def test_scope_pattern(path, scope, hit):
    assert bool(scopes.pattern(scope).search(path)) is hit


def hand_trace(devices=1):
    """Ops (ns): the bank's forward [0, 10] and backward [5, 20] overlap,
    DHS [15, 30] holds bank ops and its own, the generator [40, 50], the
    server [50, 55], a loss kernel [60, 62], an optimizer update [70, 80],
    and a ``while`` container [0, 100] over all of them."""
    names = ["%fusion.{} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop".format(i) for i in range(8)]
    names.append("%while.8 = (f32[8]{0}) while((f32[8]{0}) %t), condition=%c, body=%b")
    paths = [
        "jit(epoch_step)/ofl.gen.boost/while/body/closed_call/jvp(ofl.bank)/ofl.bank.g0/vmap()/conv",
        "jit(epoch_step)/ofl.gen.boost/while/body/closed_call/transpose(jvp(ofl.bank))/ofl.bank.g0/vmap()/conv",
        "jit(epoch_step)/ofl.kd/while/body/closed_call/ofl.dhs/transpose(jvp(ofl.bank))/ofl.bank.g0/vmap()",
        "jit(epoch_step)/ofl.kd/while/body/closed_call/ofl.dhs/jit(norm)",
        "jit(epoch_step)/ofl.gen.boost/while/body/closed_call/transpose(jvp(ofl.gen.net))/conv",
        "jit(epoch_step)/ofl.kd/while/body/closed_call/jvp(ofl.server)/dot_general",
        "jit(epoch_step)/ofl.gen.boost/while/body/closed_call/ghm_ce_fwd/pallas_call",
        "jit(epoch_step)/ofl.kd/while/body/add",
        "jit(epoch_step)/ofl.kd/while",
    ]
    ops = [[0, 0, 10], [1, 5, 15], [2, 15, 10], [3, 25, 5], [4, 40, 10], [5, 50, 5], [6, 60, 2], [7, 70, 10], [8, 0, 100]]
    devs = {str(d): {"names": list(names), "scopes": list(paths), "ops": ops,
                     "modules": [["jit_epoch_step(1)", 0, 100]]} for d in range(devices)}
    return {"window_ns": [0, 100], "devices": devs, "host": []}


def ctx_of(trace, epochs=1):
    return {"trace_data": trace, "workload": {"driver": "ofl"}, "epochs_traced": epochs}


@pytest.mark.parametrize("name, want_ns", [
    ("ofl_bank_ms", 25),  # [0, 10] u [5, 20] u [15, 25]: overlaps count once
    ("ofl_gen_net_ms", 10),
    ("ofl_server_ms", 5),
    ("ofl_dhs_ms", 15),  # [15, 25] u [25, 30]: its bank op counts here too
])
def test_readers_by_hand(name, want_ns):
    got = reader(name).read(ctx_of(hand_trace(), epochs=1))
    assert got == pytest.approx(want_ns * 1e-6)
    # averaged over the traced epochs and the chips
    assert reader(name).read(ctx_of(hand_trace(devices=2), epochs=5)) == pytest.approx(want_ns * 1e-6 / 5)


@pytest.mark.parametrize("name", list(READERS))
def test_readers_read_none_when_nothing_is_there(name):
    r = reader(name)
    assert r.read({"trace_data": None, "workload": {"driver": "ofl"}}) is None
    t = hand_trace()
    t["devices"]["0"]["scopes"] = ["jit(epoch_step)/ofl.gen.boost/while/body/conv"] * 9  # the parent's scopes
    assert r.read(ctx_of(t)) is None
    assert r.read(ctx_of({"window_ns": [0, 0], "devices": {}, "host": []})) is None


def test_readers_on_the_compiled_tiny_epoch():
    """Every reader finds its scope in the op names of the epoch program as
    the benchmark's OFL cell builds it (tiny shapes, the jnp loss path), once
    each instruction stands for an op of 1 ns."""
    import jax

    from benchlib import ofl

    cfg = common.load_json("configs", "ofl-cifar10-cnn5")
    cfg.update(image=[8, 8, 3], batch_size=4, gen_iters=2, clients=3, backend="ref")
    cfg["cnn5"] = {"conv_channels": [4, 8], "conv_kernel": 5, "fc_widths": [16, 8]}
    cfg["generator"] = {"kind": "dcgan", "latent_dim": 6, "base": 4}
    ref = common.reference_of(cfg["name"])
    prog = ofl.Program(jax, cfg, cfg["backend"])
    prog.start(ref.make_weights(jax.random.key(3), cfg))
    prog.state[6] = jax.random.key(4)  # the key the first epoch is handed
    hlo = tr.hlo_scopes(prog.hlo_text())
    prog.free()
    insts = sorted(hlo)
    dev = {"names": [f"%{i} = f32[] add()" for i in insts], "scopes": [""] * len(insts),
           "ops": [[k, 2 * k, 1] for k in range(len(insts))], "modules": [["jit_epoch_step", 0, 2 * len(insts)]]}
    t = {"window_ns": [0, 2 * len(insts)], "devices": {"0": dev}, "host": []}
    tr.attach_scopes(t, hlo, "epoch_step")
    ctx = ctx_of(t)
    values = {name: reader(name).read(ctx) for name in READERS}
    assert all(v is not None and v > 0 for v in values.values()), values
    # the network scopes nest inside the phase scopes
    gen = tr.ops_time(dev, tr.in_scope(dev, "ofl.gen.boost"), *tr.window(t))
    assert 0 < values["ofl_gen_net_ms"] <= 1000.0 * gen
    assert max(values.values()) < 1000.0 * tr.busy_s(t)
