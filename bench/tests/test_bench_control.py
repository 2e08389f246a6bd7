"""The check against the plain reference, at a size a test run holds:
a sound run passes, and with the float8 control in the program's place
the same run comes out not correct."""
import tiny


def test_sound_run_is_correct_and_the_control_is_not():
    r = tiny.run()
    checks = r["checks"]
    assert r["correct"], checks
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    limit = checks["latent_err_ratio"][1]
    assert checks["latent_err_ratio"][0] < limit / 1.5

    c = tiny.run(control=True)
    assert not c["correct"], c["checks"]
    value, limit = c["checks"]["latent_err_ratio"]
    assert value > limit
    assert c["checks"]["grouping"][0] == 0
    assert c["checks"]["unanswered"][0] == 0
