"""The warm-up copy of a paced cell's engine decides as the engine does.

Set-up serves the window's arrivals on a deep copy of the window's
engine, so that every shape is compiled before the window.  The copy
must leave the original untouched (its device state is donated by the
fused step) and must decide exactly what the original then decides.
"""
import run
import drive
import traffic


def test_fork_decides_as_the_original():
    from repro.api import EngineConfig

    cell = run.mix_cell("k8s-5k", "poisson")
    cfile = cell["config_file"]
    engine = traffic.merge(cfile["engine"], cfile["rehearsal"]["engine"])
    mix = traffic.merge(cell["mix"], cell["mix"]["rehearsal"])
    cfg = EngineConfig.from_dict(engine).evolve(alloc_backend="pallas")
    stream = traffic.arrivals(mix, 11, 2.0)
    program = drive.to_program(stream)
    t_w = stream[int(mix["warmup"]["arrivals"]) - 1][0]
    horizon = t_w + 2.0

    driver = drive.Driver(cfg)
    episode = drive.Episode()
    eng = driver.engine(episode)
    nxt = driver.run_unpaced(eng, episode, program, 0, horizon=t_w)
    before = dict(episode.binds)

    twin_episode = drive.Episode()
    twin = driver.fork(eng, twin_episode)
    driver.run_unpaced(twin, twin_episode, program, nxt, horizon=horizon)
    assert twin_episode.binds, "the copy bound nothing"
    assert episode.binds == before, "the copy's binds reached the original"

    driver.run_unpaced(eng, episode, program, nxt, horizon=horizon)
    assert {k: episode.binds[k] for k in twin_episode.binds} \
        == twin_episode.binds
    assert set(episode.binds) == set(before) | set(twin_episode.binds)
