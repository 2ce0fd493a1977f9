"""Host clock around the program's store build (`build_store`)."""


def read(rec):
    return rec.setup["store_build_s"]
